"""Distributed (multi-chip) train-step compilation.

Parity target: the reference's whole distributed execution stack —
fleet meta-optimizers rewriting programs with c_allreduce/c_broadcast
ops + ParallelExecutor NCCL handles (raw_program_optimizer.py,
details/all_reduce_op_handle.cc).

TPU-native design: ONE pjit'd train step over the global Mesh,
subclassing TrainStepCompiler (same loss/step construction) and
overriding only placement:
- every Parameter carries `dist_spec` (PartitionSpec) — set by the
  Megatron TP layers, group_sharded (ZeRO), the GPT stacked-layer
  model ('pp' on the layer dim), or None (replicated).
- the batch is sharded over 'dp' (and 'sp' for sequence parallelism).
- optimizer slot states inherit the parameter's sharding (ZeRO-ish by
  construction when 'sharding' specs are set).
- XLA/GSPMD derives ALL collectives (gradient all-reduce over dp,
  Megatron all-reduces over mp, layer-pipeline collective-permutes
  over pp, sequence all-gathers over sp) and schedules them on ICI —
  replacing every c_* op and NCCL ring of the reference.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core.tensor import Tensor
from ..monitor import sanitize as _sanitize
from . import TrainStepCompiler

__all__ = ["DistributedTrainStepCompiler", "filter_spec"]


def filter_spec(spec, mesh):
    """Drop axis names the mesh doesn't have (pp=1 runs etc.)."""
    if spec is None:
        return P()
    names = []
    for a in spec:
        if isinstance(a, (tuple, list)):
            kept = tuple(x for x in a if x in mesh.shape)
            names.append(kept if kept else None)
        else:
            names.append(a if (a is None or a in mesh.shape) else None)
    return P(*names)


class DistributedTrainStepCompiler(TrainStepCompiler):
    """pjit'd train step over a Mesh with dist_spec-driven shardings.

    usage:
        mesh = paddle_tpu.distributed.build_mesh({"dp": 2, "pp": 2, "mp": 2})
        step = DistributedTrainStepCompiler(model, opt, loss_fn, mesh,
                                            batch_specs=[P("dp"), P("dp")])
        loss = step(input_ids, labels)
    """

    def __init__(self, model, optimizer, loss_fn=None, mesh=None,
                 batch_specs=None, donate=True, accumulate_steps=1,
                 amp_level=None, amp_dtype="bfloat16",
                 amp_custom_white_list=None, amp_custom_black_list=None,
                 steps_per_dispatch=1, guard_nonfinite=False,
                 grad_scaler=None, comm_compress=True):
        """comm_compress: quantized-collective policy for the dp
        gradient allreduce (distributed.compress) — a spec string
        ("int8"/"fp8"[:ef] or the explicit "fp32" twin), a
        CompressConfig, None/False for off, or True (default) for
        $PADDLE_COMM_COMPRESS. When set, the gradient reduction
        becomes an explicit shard_map island over the data axis whose
        allreduce is measured (comm/all_reduce/{bytes,wire_bytes})
        and — for int8/fp8 — blockwise-quantized, with optional
        error-feedback residuals riding the donated step state. With
        the env unset and no argument, nothing changes: the implicit
        GSPMD psum, bit-identical to the uncompressed program."""
        from ..distributed import compress as compress_mod
        from ..distributed import mesh as mesh_mod

        super().__init__(model, optimizer, loss_fn=loss_fn, donate=donate,
                         accumulate_steps=accumulate_steps,
                         amp_level=amp_level, amp_dtype=amp_dtype,
                         amp_custom_white_list=amp_custom_white_list,
                         amp_custom_black_list=amp_custom_black_list,
                         steps_per_dispatch=steps_per_dispatch,
                         guard_nonfinite=guard_nonfinite,
                         grad_scaler=grad_scaler)
        self._mesh = mesh or mesh_mod.default_mesh()
        mesh_mod.set_mesh(self._mesh)  # activation constraints read this
        self._batch_specs = batch_specs
        self._sharded_params = False
        self._slot_shardings = None
        self._accum_shardings = {}
        self._comm_shardings = {}
        self._compress = compress_mod.resolve(comm_compress)
        # env-driven configs DISABLE on incompatible layouts (a pod
        # job sets the env once; its hybrid-mesh members keep GSPMD);
        # an explicit constructor spec raises instead
        self._compress_from_env = comm_compress is True
        self._compress_axis = None  # resolved/validated at first build
        self._compress_nranks = 1

    def _param_sharding(self, p):
        return NamedSharding(self._mesh,
                             filter_spec(getattr(p, "dist_spec", None),
                                         self._mesh))

    def _batch_sharding(self, i, ndim):
        """Data sharding for batch element i. With steps_per_dispatch
        K>1 the element carries a leading K microbatch axis that must
        stay UNSHARDED (every device runs every microstep of the scan)
        — the 'dp' shard moves to axis 1, and user batch_specs (which
        describe ONE microbatch) get a None prepended."""
        k = self._steps_per_dispatch
        if self._batch_specs is not None:
            spec = self._batch_specs[i]
            if k > 1:
                # a None entry means "replicated" (filter_spec maps it
                # to P()) — prepend the unsharded K axis to its empty
                # spec, not to None itself
                spec = P(*((None,) + (tuple(spec) if spec is not None
                                      else ())))
        else:
            lead = (None, "dp") if k > 1 else ("dp",)
            spec = P(*(lead + (None,) * (ndim - len(lead)))[:ndim])
        return NamedSharding(self._mesh, filter_spec(spec, self._mesh))

    def _microbatch_spec(self, i, ndim):
        """Sharding spec of ONE microbatch of batch element i — the
        _batch_sharding layout minus the (unsharded) K dispatch axis;
        what the compressed-gradient shard_map island splits on."""
        if self._batch_specs is not None:
            spec = self._batch_specs[i]
            spec = P(*tuple(spec)) if spec is not None else P()
        else:
            spec = P(*(("dp",) + (None,) * (ndim - 1))[:ndim])
        return filter_spec(spec, self._mesh)

    def _resolve_compress(self):
        """Validate the comm-compression config against this mesh +
        spec set (once, at first build). The quantized allreduce is
        the DATA-PARALLEL gradient reduction: it needs one >1-sized
        data axis carrying the batch, replicated parameters, and no
        other parallelism (model/pipeline shards don't have a single
        flat gradient buffer to compress — GSPMD owns those
        reductions). A hybrid mesh with compression explicitly
        requested is a loud error; a degenerate data axis (W<2) just
        disables it."""
        cfg = self._compress
        if cfg is None:
            return None

        def _incompatible(why):
            if not self._compress_from_env:
                raise ValueError(
                    f"comm_compress={cfg.spec()!r}: {why}")
            from ..core import monitor as _cmon

            self._compress = None
            try:
                _cmon.VLOG(1, f"comm_compress={cfg.spec()} "
                              f"(PADDLE_COMM_COMPRESS): {why} — "
                              "disabled for this compiler")
            except Exception:
                pass
            return None

        mesh = self._mesh
        if self._batch_specs is not None:
            leads = set()
            for s in self._batch_specs:
                entry = tuple(s)[0] if s is not None and tuple(s) \
                    else None
                if isinstance(entry, (tuple, list)):
                    entry = tuple(entry)
                if entry is not None:
                    leads.add(entry)
            if len(leads) > 1:
                return _incompatible(
                    "batch elements shard their leading dim over "
                    f"different axes {sorted(map(str, leads))} — "
                    "one data axis is required")
            lead = leads.pop() if leads else None
        else:
            lead = "dp"
        if isinstance(lead, tuple):
            if len(lead) != 1:
                return _incompatible(
                    f"the batch is sharded over multiple axes "
                    f"{lead} — the quantized allreduce runs over "
                    "ONE data axis")
            lead = lead[0]
        W = int(mesh.shape[lead]) if lead in mesh.shape else 1
        if W < 2:
            from ..core import monitor as _cmon

            self._compress = None
            try:
                _cmon.VLOG(1, f"comm_compress={cfg.spec()}: data "
                              f"axis {lead!r} has {W} shard(s) — "
                              "nothing to compress, disabled")
            except Exception:
                pass
            return None
        others = [a for a in mesh.axis_names
                  if a != lead and int(mesh.shape[a]) > 1]
        if others:
            return _incompatible(
                f"needs a pure data-parallel mesh, but axes "
                f"{others} are also >1 — GSPMD owns the model/"
                "pipeline reductions on hybrid layouts")
        mp = P()
        for coll in (dict(self._model.named_parameters()),
                     dict(self._model.named_buffers())):
            for name, p in coll.items():
                if filter_spec(getattr(p, "dist_spec", None),
                               mesh) != mp:
                    return _incompatible(
                        f"needs replicated parameters, but {name!r}"
                        f" carries dist_spec="
                        f"{getattr(p, 'dist_spec', None)!r}")
        self._compress_axis = lead
        self._compress_nranks = W
        return cfg

    def _init_comm_state(self, t_items):
        """Error-feedback residual state: ONE flat f32 buffer per
        rank ((W, L) globally, sharded over the data axis), L = the
        packed gradient length padded to the allreduce's W*block
        multiple. Donated with the rest of the step state; PTA080
        flags the never-donated configuration."""
        cfg = self._resolve_compress()
        self._comm_shardings = {}
        if cfg is None or not cfg.ef:
            return {}
        from ..analysis.compress import guard_residual_donated
        from ..distributed import compress as compress_mod

        guard_residual_donated(
            self._donate, cfg,
            where=f"train_step:{type(self._model).__name__}")
        segs = compress_mod.pack.segments(
            [k for k, _ in t_items],
            {k: p._value for k, p in t_items})
        L = compress_mod.padded_elems(
            cfg, compress_mod.pack.total_elems(segs),
            self._compress_nranks)
        sh = NamedSharding(self._mesh, P(self._compress_axis))
        self._comm_shardings = {"residual": sh}
        arr = np.zeros((self._compress_nranks, L), np.float32)
        return {"residual": jax.device_put(arr, sh)}

    def _grads_and_loss(self, loss_of, pvals, fvals, bvals, avals,
                        rngc, scale, comm):
        """Compressed-gradient override: the forward/backward runs
        per-shard inside a shard_map island over the data axis, the
        local gradients are unscaled (GradScaler) BEFORE quantizing,
        packed into one flat buffer and pushed through the quantized
        allreduce (distributed.compress.reduce_tree — SUM then /W,
        the dp MEAN the GSPMD path computes implicitly); loss and
        float buffer updates pmean across shards. Uncompressed
        compilers keep the base path (implicit GSPMD reduction),
        bit-identical to pre-compression programs."""
        cfg = self._compress
        if cfg is not None and self._compress_axis is None:
            # state adopted from a sibling: the adopt carried the
            # residuals but not the (idempotent) axis resolution
            cfg = self._resolve_compress()
        if cfg is None:
            return super()._grads_and_loss(
                loss_of, pvals, fvals, bvals, avals, rngc, scale,
                comm)
        from jax import lax

        from ..distributed import compress as compress_mod
        from ..distributed import mesh as mesh_mod

        ax, W = self._compress_axis, self._compress_nranks
        use_scale = self._grad_scaler is not None
        names = list(pvals.keys())
        model_name = type(self._model).__name__

        def island(pv, fv, bv, av, rc, sc, cm):
            if use_scale:
                def scaled_loss_of(pv_, fv_, bv_, av_, rc_):
                    loss, nb = loss_of(pv_, fv_, bv_, av_, rc_)
                    return loss * sc, (loss, nb)

                (_, (loss, nb)), grads = jax.value_and_grad(
                    scaled_loss_of, has_aux=True)(pv, fv, bv, av, rc)
                inv = np.float32(1.0) / sc
                grads = {n: (g.astype(jnp.float32) * inv).astype(
                    g.dtype) for n, g in grads.items()}
            else:
                (loss, nb), grads = jax.value_and_grad(
                    loss_of, has_aux=True)(pv, fv, bv, av, rc)
            segs = compress_mod.pack.segments(names, grads)
            total = compress_mod.pack.total_elems(segs)
            compress_mod.account(
                cfg, total * 4,
                compress_mod.padded_elems(cfg, total, W),
                where=f"train_step:{model_name}",
                block=compress_mod.effective_block(cfg, total, W))
            residual = cm.get("residual")
            res_local = residual[0] if residual is not None else None
            grads, new_res = compress_mod.reduce_tree(
                grads, segs, ax, W, cfg, residual=res_local)
            loss = lax.pmean(loss, ax)
            nb = {k: (lax.pmean(v, ax)
                      if jnp.issubdtype(v.dtype, jnp.inexact) else v)
                  for k, v in nb.items()}
            new_cm = dict(cm)
            if residual is not None:
                new_cm["residual"] = new_res[None]
            return loss, nb, grads, new_cm

        aval_specs = tuple(self._microbatch_spec(i, np.ndim(a))
                           for i, a in enumerate(avals))
        repl = P()
        body = mesh_mod.shard_map_compat(
            island, self._mesh,
            (repl, repl, repl, aval_specs, repl, repl, P(ax)),
            (repl, repl, repl, P(ax)))
        return body(pvals, fvals, bvals, avals, rngc, scale, comm)

    @staticmethod
    def _hostify(v):
        """Multi-process: device_put of a process-local jax.Array onto
        a global (cross-process) sharding is rejected; route through
        host memory (every process holds the same value by seed
        discipline — the c_broadcast-at-startup analog)."""
        if jax.process_count() > 1:
            return np.asarray(v)
        return v

    # -- hook overrides ---------------------------------------------------
    def _prepare_call(self, trainable, frozen, bufs):
        if self._sharded_params:
            return
        # place parameter arrays per dist_spec (c_broadcast-at-startup
        # analog — a single device_put onto the mesh)
        for coll in (trainable, frozen, bufs):
            for p in coll.values():
                p._value = jax.device_put(self._hostify(p._value),
                                          self._param_sharding(p))
        self._sharded_params = True

    def _place_batch(self, batch):
        out = []
        for i, b in enumerate(batch):
            v = b._value if isinstance(b, Tensor) else jnp.asarray(b)
            out.append(jax.device_put(self._hostify(v),
                                      self._batch_sharding(i, v.ndim)))
        return tuple(out)

    def _slot_sharding(self, p):
        """Optimizer-state sharding: ZeRO stage 2 ('os_g') tags params
        with `slot_dist_spec` (slots sharded, params replicated); stage
        3 shards the param itself, which slots inherit."""
        spec = getattr(p, "slot_dist_spec", None)
        if spec is not None:
            return NamedSharding(self._mesh, filter_spec(spec, self._mesh))
        return self._param_sharding(p)

    def _init_opt_state(self, t_items):
        super()._init_opt_state(t_items)
        # shard optimizer slots like their parameters (ZeRO pattern when
        # 'sharding' specs are present)
        self._slot_shardings = {}
        self._accum_shardings = {}
        repl = NamedSharding(self._mesh, P())
        for k, p in t_items:
            psh = self._slot_sharding(p)
            slots = {}
            for sname, sval in self._opt_state[k].items():
                same_shape = tuple(np.shape(sval)) == tuple(p._value.shape)
                sh = psh if same_shape else repl
                slots[sname] = sh
                self._opt_state[k][sname] = jax.device_put(
                    self._hostify(sval), sh)
            self._slot_shardings[k] = slots
        # gradient-merge buffers: stage 2 tags accum_dist_spec (sharded
        # merged grads); otherwise they follow the param's own sharding
        # (stage 3: sharded; plain runs: replicated)
        for k, p in t_items:
            if k in self._accum_state:
                aspec = getattr(p, "accum_dist_spec", None)
                sh = (NamedSharding(self._mesh,
                                    filter_spec(aspec, self._mesh))
                      if aspec is not None else self._param_sharding(p))
                self._accum_shardings[k] = sh
                self._accum_state[k] = jax.device_put(
                    self._hostify(self._accum_state[k]), sh)

    def _lint_shardings(self, batch):
        """PTA05x sharding-spec lints just before the first compile:
        hand-written batch_specs/dist_specs that name unknown mesh
        axes (silently replicated by filter_spec), don't divide their
        dims, miss batch elements, or leave large parameters
        replicated on a model-parallel mesh — caught here instead of
        at dispatch. Report-only under PADDLE_ANALYSIS=1;
        PADDLE_SANITIZE=sharding makes error findings abort the
        build."""
        from ..analysis import enabled as _analysis_enabled

        if not (_sanitize._sharding or _analysis_enabled()):
            return
        from ..analysis import sharding as _shlint

        report = _shlint.check_compiler(self, batch)
        if _sanitize._sharding and report.errors:
            raise ValueError(
                "PTA05x sharding-spec lint failed "
                "(PADDLE_SANITIZE=sharding):\n"
                + "\n".join(f.format() for f in report.errors))

    def _jit_step(self, step_fn, trainable, frozen, bufs, batch):
        self._lint_shardings(batch)
        mesh = self._mesh
        repl = NamedSharding(mesh, P())
        param_sh = {k: self._param_sharding(p)
                    for k, p in trainable.items()}
        frozen_sh = {k: self._param_sharding(p)
                     for k, p in frozen.items()}
        buf_sh = {k: repl for k in bufs}
        batch_sh = []
        for i, b in enumerate(batch):
            v = b._value if isinstance(b, Tensor) else np.asarray(b)
            batch_sh.append(self._batch_sharding(i, np.ndim(v)))
        # inputs: (params, slots, accum, comm residuals, frozen,
        # buffers, batch, lr, rngc, loss_scale); outputs add the
        # replicated per-microstep nonfinite-skip flags after the
        # losses, then the numerics-probe stats tree (empty pytree —
        # zero leaves — unless PADDLE_SANITIZE=numerics was armed at
        # build; `repl` is a pytree prefix, so it covers both)
        in_shardings = (param_sh, self._slot_shardings,
                        self._accum_shardings, self._comm_shardings,
                        frozen_sh, buf_sh, tuple(batch_sh), repl,
                        repl, repl)
        out_shardings = (param_sh, self._slot_shardings,
                        self._accum_shardings, self._comm_shardings,
                        buf_sh, repl, repl, repl)
        return super()._jit_step(step_fn, trainable, frozen, bufs, batch,
                                 in_shardings=in_shardings,
                                 out_shardings=out_shardings)
