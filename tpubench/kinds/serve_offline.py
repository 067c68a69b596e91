"""Traffic kind `serve_offline`: a backlog present from the start, the batch
kept full, through LLMEngine. The window opens when the batch is full and its
prefills are done, and counts whole engine steps; the work of a step is the
tokens it emitted. Requests still running at the end of the window are
aborted: an offline batch is judged on tokens per second, and `attempted` are
the requests the engine admitted. The check reads what each had emitted by
then, so a run ends with its window and waits for no sequence to finish; the
finishing path is checked on the warm-up's requests (serve_common).
"""
from __future__ import annotations

import time

from .. import core
from ..estimators import whole_steps
from ..trafficgen import backlog, block_multisets
from . import serve_common as sc


def run(run):
    fam = core.family(run.config)
    mix = run.traffic
    engine = fam.build_engine(run.config, run.seed)
    core.say(f"KV pool {engine.cache.num_blocks} blocks of "
             f"{engine.block_size}, max_batch {engine.max_batch}")
    sc.warm_engine(run, engine, block_multisets(mix)[0])

    entries = backlog(mix, run.seed,
                      int(mix["backlog_factor"]) * engine.max_batch,
                      run.config["vocab_size"],
                      int(run.config["n_positions"]))
    rids = [engine.add_request(e["prompt"],
                               sampling=sc.sampling(run, e["answer_len"]))
            for e in entries]
    stepper = sc.Stepper(run, engine)
    while len(engine.scheduler.running) < engine.max_batch \
            and engine.has_unfinished():
        stepper.step()
    t_open = run.open_window()
    ends = []
    while engine.has_unfinished() \
            and (not ends or ends[-1] - t_open <= run.seconds):
        if run.trace_due(time.perf_counter() - t_open):
            run.start_trace()
        ends.append(stepper.step())
    run.stop_trace()
    ends = whole_steps(ends, t_open, run.seconds)
    t_close = ends[-1] if ends else t_open
    run.close_window(t_open, t_close)

    reqs = [engine.get_request(r) for r in rids]
    started = [r for r in reqs if r.token_times]
    finished = [r for r in started if r.state == "finished"]
    sc.count_prefills(stepper.records, [r.token_times[0] for r in started])
    steps = stepper.in_window(t_open, t_close)
    sc.step_samples(run, engine, steps)
    run.samples["step_end_s"] = [s["end"] - t_open for s in steps]
    run.samples["step_work"] = [s["tokens"] for s in steps]
    run.write_log("steps.jsonl", [
        {**s, "start": s["start"] - t_open, "end": s["end"] - t_open}
        for s in stepper.records])

    for rid in rids:
        engine.abort_request(rid)
    core.say(f"{len(started)} requests admitted, {len(finished)} finished, "
             f"{len(steps)} engine steps in the window")
    run.attempted = len(started)
    run.correct, run.failed = sc.check_outputs(run, engine, started)
