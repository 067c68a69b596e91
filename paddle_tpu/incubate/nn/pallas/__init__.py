"""paddle.incubate.nn.pallas — the fused Pallas TPU kernel library.

Reference capability surface: paddle/fluid/operators/fused/ — the
CUDA fused_bias_dropout_residual_layer_norm / fused_gelu epilogues and
the multi-tensor fused_adam/merged_momentum optimizer kernels. Here
each is ONE Pallas kernel (forward AND backward) instead of a chain of
XLA fusions:

- `layernorm.fused_layer_norm` / `fused_residual_layer_norm`: LayerNorm
  with optional residual-add prologue and GeLU epilogue — one VMEM pass
  over the activation per direction (the unfused composition re-reads
  it once per op).
- `optim.apply_fused`: multi-tensor optimizer update (Adam/AdamW/SGD/
  Momentum) over the flattened parameter set — one kernel launch per
  step instead of a per-parameter tree of fusions.

These two are OFF by default and numerics-neutral when off:
`PADDLE_PALLAS_FUSION=1` arms the fused paths on TPU backends;
`PADDLE_PALLAS_INTERPRET=1` additionally lets them run through the
Pallas interpreter on CPU (parity tests / debugging — slow, never for
production CPU runs). Every wired call site picks the kernel from a
static shape/platform predicate (`ln_supported`, `optim_supported`)
and takes the unfused composition when it says no; a selected kernel
that fails to compile fails the step.

- `paged_attention`: the serving engine's decode and verify attention
  through the block tables. It reads no switch: its predicate
  (`paged_attention.paged_decode_supported`) answers from platform,
  mesh and shape alone (on the CPU, from `PADDLE_PALLAS_INTERPRET`).
- `grouped_matmul`: the dropless experts' two products a layer
  (`moe/dropless.py`), a read of the hit experts' weights. No switch
  either: `grouped_matmul.grouped_matmul_supported`.
"""
from __future__ import annotations

import os

__all__ = ["fusion_enabled", "interpret_mode", "kernels_available",
           "ln_supported", "optim_supported", "layernorm", "optim",
           "paged_attention", "grouped_matmul",
           "fused_layer_norm", "fused_residual_layer_norm"]

def _env_on(name, default="0"):
    return os.environ.get(name, default) not in ("0", "", "false",
                                                 "False", "off")


def fusion_enabled():
    """Master switch for the fused-kernel call sites
    (PADDLE_PALLAS_FUSION=1)."""
    return _env_on("PADDLE_PALLAS_FUSION")


def interpret_mode():
    """Run the kernels through the Pallas interpreter
    (PADDLE_PALLAS_INTERPRET=1): CPU parity testing only."""
    return _env_on("PADDLE_PALLAS_INTERPRET")


def _on_tpu():
    import jax

    return jax.devices()[0].platform == "tpu"


def kernels_available():
    """Fusion armed AND a backend that can run the kernels: a real TPU,
    or the interpreter when explicitly requested."""
    return fusion_enabled() and (_on_tpu() or interpret_mode())


def _partitioned():
    """Is a multi-device mesh live? Compiled Mosaic kernels cannot be
    split by GSPMD ("Mosaic kernels cannot be automatically
    partitioned. Please wrap the call in a shard_map"): a kernel with
    no shard_map island of its own is not supported there."""
    from ....distributed import mesh as mesh_mod

    mesh = mesh_mod.get_mesh()
    return mesh is not None and mesh.size > 1


def ln_supported(hidden):
    """Can the fused LayerNorm kernels take this last-dim size here?
    Compiled TPU kernels want a lane-aligned hidden dim and no
    multi-device mesh; the interpreter takes anything (odd-shape
    parity tests)."""
    if not fusion_enabled():
        return False
    if _on_tpu():
        return hidden % 128 == 0 and not _partitioned()
    return interpret_mode()


def optim_supported():
    """Can the fused multi-tensor optimizer kernels run here? On TPU
    only outside a multi-device mesh (the packed buffers have no
    sharding of their own)."""
    return kernels_available() and not (_on_tpu() and _partitioned())


# the kernel submodules pull in jax.experimental.pallas (and, on TPU,
# the Mosaic backend) — keep them LAZY so `import paddle_tpu` (which
# reaches here through incubate.nn) doesn't pay that at startup with
# the feature off; call sites go through these attributes, which load
# on first touch (PEP 562)
def __getattr__(name):
    if name in ("layernorm", "optim", "paged_attention",
                "grouped_matmul"):
        import importlib

        return importlib.import_module("." + name, __name__)
    if name in ("fused_layer_norm", "fused_residual_layer_norm"):
        from . import layernorm

        return getattr(layernorm, name)
    raise AttributeError(
        f"module {__name__!r} has no attribute {name!r}")
