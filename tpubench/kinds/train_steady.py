"""Traffic kind `train_steady`: a fixed token batch per step, fresh seeded
batches from a host iterator, through the compiled train step.

Timing. Each iteration takes the next batch, dispatches a step and fetches the
*previous* step's loss, so the program's own dispatch overlap stays as users
get it; the clock is read after that fetch. The window opens at the end of the
last warm-up iteration and counts the iterations that end inside `--seconds`:
whole steps only. Every step's wall time goes to steps.jsonl.
"""
from __future__ import annotations

import math
import queue
import threading
import time

from .. import core
from ..estimators import whole_steps
from ..trafficgen import MarkovTokens


class _Prefetch:
    """Batches made on one background thread, a few ahead."""

    def __init__(self, make, depth):
        self._q = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._fill, args=(make,),
                                        daemon=True)
        self._thread.start()

    def _fill(self, make):
        while not self._stop.is_set():
            item = make()
            while not self._stop.is_set():
                try:
                    self._q.put(item, timeout=0.1)
                    break
                except queue.Full:
                    continue

    def __next__(self):
        return self._q.get()

    def close(self):
        self._stop.set()
        self._thread.join(timeout=10)


def run(run):
    fam = core.family(run.config)
    tr = run.traffic
    rows = int(tr["sequences_per_chip"]) * run.chips
    seq = int(tr["sequence_length"])
    tokens_per_step = rows * seq
    run.values.update(tokens_per_step=tokens_per_step, rows_per_chip=rows
                      // run.chips, sequence_length=seq)
    chain = MarkovTokens(run.seed, run.config["vocab_size"],
                         tr["markov_successors"], tr["markov_skew"])
    first = chain.batch(rows, seq)

    # the reference, outside the window: the same seeded weights, forward
    # only, dense attention, a few rows at a time so the [S, S] scores fit
    with run.span("reference_loss"):
        ref = fam.build_train_model(run.config, run.seed, scan_unroll=1,
                                    flash=False)
        loss_dense = fam.forward_loss(
            ref, first, int(run.config["train"]["reference_rows"]))
        del ref
    core.say(f"step-0 loss, forward only, dense attention: {loss_dense:.5f}")

    model = fam.build_train_model(run.config, run.seed)
    step = fam.build_train_step(run.config, model, run.chips)
    batches = _Prefetch(lambda: chain.batch(rows, seq), int(tr["prefetch"]))
    losses, state = [], {"prev": None, "next": first}

    def one_step():
        with run.span("next_batch"):
            ids = state["next"]
            state["next"] = None
            if ids is None:
                ids = next(batches)
        with run.span("train_step"):
            loss = step(ids, ids)
        with run.span("loss_fetch"):
            if state["prev"] is not None:
                losses.append(float(state["prev"].item()))
        state["prev"] = loss
        return time.perf_counter()

    try:
        # call 0 compiles, the memory capture compiles a second module and
        # call 1 goes through a second jit cache entry (PERF.md, PR 21):
        # those two, then `warmup_steps` more, are set-up
        t = one_step()
        core.say(f"first call {t - run.t_start:.1f} s after process start")
        for _ in range(1 + int(tr["warmup_steps"])):
            t = one_step()
        t_open = run.open_window()
        ends = []
        while not ends or ends[-1] - t_open <= run.seconds:
            if run.trace_due(time.perf_counter() - t_open):
                run.start_trace()
            ends.append(one_step())
        run.stop_trace()
        ends = whole_steps(ends, t_open, run.seconds)
        run.close_window(t_open, ends[-1] if ends else t_open)
        losses.append(float(state["prev"].item()))
    finally:
        batches.close()

    run.samples["step_end_s"] = [e - t_open for e in ends]
    run.samples["step_work"] = [tokens_per_step / run.chips] * len(ends)
    edges = [t_open] + ends
    run.write_log("steps.jsonl", [
        {"step": i, "end_s": b - t_open, "wall_s": b - a}
        for i, (a, b) in enumerate(zip(edges, edges[1:]))])
    run.write_log("losses.jsonl", [{"call": i, "loss": v}
                                   for i, v in enumerate(losses)])

    tol = float(run.config["train"]["loss_tolerance"])
    n = min(10, len(losses) // 2)
    head, tail = losses[:n], losses[-n:]
    checks = {
        "step-0 loss equals dense forward": abs(losses[0] - loss_dense) <= tol,
        "every loss finite": all(math.isfinite(v) for v in losses),
        "loss falls": sum(tail) / n < sum(head) / n,
    }
    core.say(f"loss {losses[0]:.5f} (dense {loss_dense:.5f}) -> "
             f"{losses[-1]:.5f} over {len(losses)} calls; "
             f"first {n} mean {sum(head) / n:.4f}, last {n} mean "
             f"{sum(tail) / n:.4f}; checks {checks}")
    run.correct = all(checks.values())
    run.attempted = len(ends)
    run.failed = 0
