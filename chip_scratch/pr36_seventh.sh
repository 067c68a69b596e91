#!/bin/sh
# PR 36, after the review: six alternating pairs of --trace 0 on serve-345m-offline-decode, the parent 44f785e
# (chip_scratch/parent) against the committed files of the final tree (chip_scratch/archive); each run's steps.jsonl
# is kept, so that a run that held a freeze can be told and the median step compared beside serve_tok_s.
R="sh chip_scratch/pr36_run.sh"; P=chip_scratch/parent; A=chip_scratch/archive; G=serve-345m-offline-decode
$R pr36j parent $P $G 2147500301 0 change $A $G 2147500301 0  change $A $G 2147500302 0 parent $P $G 2147500302 0 \
  parent $P $G 2147500303 0 change $A $G 2147500303 0  change $A $G 2147500304 0 parent $P $G 2147500304 0 \
  parent $P $G 2147500305 0 change $A $G 2147500305 0  change $A $G 2147500306 0 parent $P $G 2147500306 0
