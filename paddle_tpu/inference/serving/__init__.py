"""paddle_tpu.inference.serving — the TPU-native serving engine.

Reference capability: paddle/fluid/inference (the 61k-LoC deployment
stack). Here the generation path is rebuilt around the TPU serving
designs in PAPERS.md — Ragged Paged Attention (arxiv 2604.15464) and
the Gemma-on-Cloud-TPU serving comparison (arxiv 2605.25645):

  * `kv_cache`      block-allocated paged KV cache: fixed-size blocks
                    in preallocated device pools, per-request block
                    tables, alloc/free/defrag + admission control;
                    per-slot state arrays behind the pools
  * `scheduler`     continuous batching: FIFO admit / youngest-first
                    evict / preempt between fused decode dispatches
  * `model_runner`  the RUNNER the engine reads of a model (params,
                    cache rows, prefill/decode/verify/tail programs),
                    one of two; GPT-2's compiled prefill + paged
                    decode programs, per-request in-program sampling
  * `state_runner`  the other runner, of every model that hands the
                    serving path its layers (`decoder_layers`: GLM,
                    LongCat, LFM2, Mellum, Falcon-H1): one pool of
                    latent rows or K/V pools with grouped heads,
                    read through the block tables by a Pallas kernel
                    on a TPU, and beside them states of fixed size a
                    sequence in per-slot arrays
  * `engine`        `LLMEngine.generate()` / `add_request()`
                    streaming front end, donated decode step through
                    the persistent compile cache; ISSUE-13 lifecycle
                    (drain/export/timeout/watchdog emergency export)
  * `router`        `Router` — N health-checked threaded replicas,
                    least-loaded routing, deterministic token-exact
                    failover (ISSUE 13)

The ragged paged-attention decode kernels themselves (K/V pools;
latent rows) live with their PR-8 siblings in
`incubate.nn.pallas.paged_attention`.
"""
from __future__ import annotations

from .engine import EngineTimeout, LLMEngine
from .kv_cache import (BlockAllocator, NULL_BLOCK, PagedKVCache,
                       env_block_size, env_max_batch, env_pool_bytes)
from .autoscaler import Autoscaler, maybe_autoscale
from .router import Router, env_heartbeat_s, env_replicas
from .scheduler import (EngineOverloaded, Request, SamplingParams,
                        Scheduler, env_deadline_s, env_max_queue)

__all__ = ["LLMEngine", "SamplingParams", "Request", "Scheduler",
           "Router", "EngineOverloaded", "EngineTimeout",
           "PagedKVCache", "BlockAllocator", "NULL_BLOCK",
           "env_block_size", "env_max_batch", "env_pool_bytes",
           "env_max_queue", "env_deadline_s", "env_replicas",
           "env_heartbeat_s", "Autoscaler", "maybe_autoscale"]
