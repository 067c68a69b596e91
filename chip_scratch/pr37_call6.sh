#!/bin/sh
python chip_scratch/pr37_flash_smoke.py
sh chip_scratch/pr37_dump.sh
