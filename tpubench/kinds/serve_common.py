"""What the serving kinds share: warming the engine, stepping it under the
benchmark's spans with one record per step, and the check of its outputs."""
from __future__ import annotations

import time

import numpy as np

from .. import core


def sampling(run, answer_len):
    from paddle_tpu.inference.serving import SamplingParams

    return SamplingParams(max_new_tokens=int(answer_len),
                          temperature=run.config["serve"]["temperature"])


def warm_engine(run, engine, prompt_lengths):
    """One short request per padded prompt length the traffic will use, run
    to the end: compiles (or loads) exactly those prefill programs and the
    decode program. Each has to finish with all its tokens."""
    from paddle_tpu.core.monitor import stat_get

    rng = np.random.default_rng([run.seed, 0xA11])
    vocab = run.config["vocab_size"]
    faults = ("serve/evictions", "serve/oom_evictions")
    before = [stat_get(n) for n in faults]
    with run.span("warm_engine"):
        rids = [engine.add_request(rng.integers(1, vocab, plen).tolist(),
                                   sampling=sampling(run, 2))
                for plen in sorted(set(prompt_lengths))]
        while engine.has_unfinished():
            engine.step()
    reqs = [engine.get_request(r) for r in rids]
    if not all(r.state == "finished" and len(r.output_ids) == 2
               for r in reqs):
        raise RuntimeError("a warm-up request did not finish with its two "
                           f"tokens: {[(r.state, len(r.output_ids)) for r in reqs]}")
    if [stat_get(n) for n in faults] != before:
        # the engine answers a decode program that does not fit by evicting
        # and re-prefilling, which "finishes" a two-token request
        raise RuntimeError("the engine evicted during warm-up: the decode "
                           "program did not run; see the log above")
    core.say(f"warmed prefill lengths {sorted(set(prompt_lengths))} and the "
             f"decode program, {time.perf_counter() - run.t_start:.1f} s "
             "after process start")


class Stepper:
    """engine.step() under a span, with one record per step."""

    def __init__(self, run, engine):
        from paddle_tpu.core.monitor import stat_get

        self._run, self._engine, self._get = run, engine, stat_get
        self.records = []

    def step(self):
        g, e = self._get, self._engine
        before = (g("serve/tokens"), g("serve/prefill_us"),
                  g("serve/decode_us"))
        t0 = time.perf_counter()
        with self._run.span("engine_step"):
            e.step()
        t1 = time.perf_counter()
        running = list(e.scheduler.running.values())
        self.records.append({
            "start": t0, "end": t1,
            "tokens": g("serve/tokens") - before[0],
            "prefill_us": g("serve/prefill_us") - before[1],
            "decode_us": g("serve/decode_us") - before[2],
            "running": len(running),
            "ctx_tokens": sum(r.context_len for r in running),
            "waiting": len(e.scheduler.waiting),
            "kv_used": g("serve/kv_blocks/used")})
        return t1

    def in_window(self, lo, hi):
        return [r for r in self.records if lo <= r["end"] <= hi]


def step_samples(run, engine, steps):
    """The per-step samples and window figures the readers use, from the
    window's steps."""
    run.samples["decode_step_ms"] = [s["decode_us"] / 1e3 for s in steps
                                     if s["decode_us"] > 0]
    run.samples["step_ctx_tokens"] = [s["ctx_tokens"] for s in steps]
    # tokens a decode dispatch emitted, over its fixed width: a step's first
    # tokens come from its prefills, one per admitted request
    run.samples["batch_occupancy"] = [
        max(0, s["tokens"] - s["prefills"]) / engine.max_batch
        for s in steps if s["decode_us"] > 0]
    run.samples["kv_used_share"] = [
        s["kv_used"] / (engine.cache.num_blocks - 1) for s in steps]


def count_prefills(steps, first_token_times):
    """Sets steps[i]["prefills"]: first tokens that fell inside the step."""
    firsts = sorted(first_token_times)
    j = 0
    for s in steps:
        n = 0
        while j < len(firsts) and firsts[j] <= s["end"]:
            n += firsts[j] >= s["start"]
            j += 1
        s["prefills"] = n


def check_outputs(run, engine, requests):
    """`requests`: Request objects that emitted at least one token, finished
    or aborted. Returns (correct, failed). A seeded sample is teacher-forced
    through the family's plain float32 reference: every emitted token's
    reference logit has to lie within the stated margin of the largest. A
    finished request that did not return all its tokens is `failed`, and the
    engine may have leaked no block."""
    fam = core.family(run.config)
    s = run.config["serve"]
    rng = np.random.default_rng([run.seed, 0xC4EC])
    cut = [r for r in requests if r.state == "finished"
           and len(r.output_ids) != r.sampling.max_new_tokens]
    pick = rng.choice(len(requests), size=min(int(s["check_requests"]),
                                              len(requests)), replace=False)
    worst, checked = 0.0, 0
    with run.span("reference_check"):
        for i in pick:
            r = requests[int(i)]
            d = fam.teacher_forced_deficits(
                engine.params, int(run.config["n_head"]), r.prompt_ids,
                r.output_ids, int(run.config["n_positions"]))
            worst = max(worst, float(d.max()))
            checked += len(r.output_ids)
    leaks = engine.check_drained()
    checks = {
        "every finished request returned all its tokens": not cut,
        f"emitted logits within {s['logit_margin']} of the reference's "
        "largest": checked > 0 and worst <= float(s["logit_margin"]),
        "check_drained() empty": not leaks,
    }
    core.say(f"reference check on {len(pick)} requests, {checked} emitted "
             f"tokens: largest deficit {worst:.5f}; leaks {leaks}; checks "
             f"{checks}")
    return all(checks.values()), len(cut)
