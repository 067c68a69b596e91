"""PR 37: the train cell's step compiled HERE for a described v5e (no
chip, nothing runs): the compiler's memory_analysis, whose temp bytes
are the part of `peak_hbm_gib.train` that a kernel's layout can move.

    cd <tree> && JAX_PLATFORMS=cpu python <repo>/chip_scratch/pr37_compile_here.py [rows]

Run from the root of the tree to read (the repo, chip_scratch/parent).
A compile-time figure, not a chip run.
"""
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.getcwd())

import jax  # noqa: E402
import numpy as np  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

jax.config.update("jax_enable_compilation_cache", False)

from paddle_tpu.incubate.nn import pallas as _pallas  # noqa: E402
from tpubench.models import gpt2 as fam  # noqa: E402

_pallas._on_tpu = lambda: True     # the described chip, not the CPU
rows = int(sys.argv[1]) if len(sys.argv) > 1 else 12
config = json.load(open("tpubench/configs/gpt2-345m-train.json"))
model = fam.build_train_model(config, 1)
step = fam.build_train_step(config, model, 1)
ids = np.zeros((rows, 1024), np.int32)
trainable, frozen, bufs = step._params_and_buffers()
step._prepare_call(trainable, frozen, bufs)
step._build(trainable, frozen, bufs, (ids, ids))

topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
chip = SingleDeviceSharding(topo.devices[0])


def described(tree):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype
                                       if not hasattr(a, "dtype")
                                       else a.dtype, sharding=chip), tree)


args = (
    {k: p._value for k, p in trainable.items()}, step._opt_state,
    step._accum_state, step._comm_state,
    {k: p._value for k, p in frozen.items()},
    {k: b._value for k, b in bufs.items()}, (ids, ids),
    np.float32(1e-4), np.uint32(0), np.float32(1.0))
compiled = step._program.lower(*described(args)).compile()
mem = compiled.memory_analysis()
hlo = compiled.as_text()
print(json.dumps({
    "tree": os.getcwd(), "rows": rows,
    "temp_bytes": mem.temp_size_in_bytes,
    "argument_bytes": mem.argument_size_in_bytes,
    "output_bytes": mem.output_size_in_bytes,
    "alias_bytes": mem.alias_size_in_bytes,
    "tpu_custom_calls": hlo.count('custom_call_target="tpu_custom_call"'),
}))
