"""Closed-loop fleet serving: ``clients`` clients, each sending its next
request when its last one completes, over one ``ServeEngine``.

Requests come from a replay set of ``replay`` requests per seed.  Every
seed has the same set of prompt and output lengths (the quantiles of two
lognormals, clipped, paired in a fixed order); the seed draws the tokens
and the order.  Set-up compiles every shape the window uses (one request
per distinct prompt length, two tokens each), then submits one request per
client and ticks until each has its first token.  The window is a whole
number of engine ticks.

After the window a sample of the finished requests, drawn from the seed
with the longest among them, is run through the reference: the number
compared is the widest gap by which a served (greedy) token's reference
logit lies below the reference's best at that position.
"""
from __future__ import annotations

import gc
import statistics
import time

import jax
import numpy as np

from bench import common, weights


def replay_set(mix, vocab, seed):
    """[(prompt tokens, max_new_tokens)] for one seed."""
    n = mix["replay"]
    z = np.array([statistics.NormalDist().inv_cdf((i + 0.5) / n)
                  for i in range(n)])

    def lengths(key):
        med, sigma, lo, hi = mix[key]
        return np.clip(np.rint(med * np.exp(sigma * z)), lo, hi).astype(int)

    prompts = lengths("prompt_lognormal")
    outputs = lengths("output_lognormal")[np.random.default_rng(0).permutation(n)]
    rng = np.random.default_rng(seed)
    return [(rng.integers(0, vocab, size=int(prompts[i]), dtype=np.int32),
             int(outputs[i])) for i in rng.permutation(n)]


def run(r):
    from repro.models import Model
    from repro.serving.engine import ServeEngine
    mix, config = r.mix, r.config
    cfg = common.program_config(config)
    weights.check_layout(config, Model(cfg).abstract())
    page, max_len = mix["page_size"], mix["max_len"]
    eng = ServeEngine(cfg, world_size=mix["world_size"], backend=mix["flavor"],
                      max_len=max_len, page_size=page,
                      n_pages=mix["max_running"] * -(-max_len // page),
                      max_running=mix["max_running"], seed=r.seeds["weights"])
    prefill = eng.prefill_fn

    def timed_prefill(params, batch):
        with r.rec.span("prefill"):
            return jax.block_until_ready(prefill(params, batch))

    eng.prefill_fn = timed_prefill
    replay = replay_set(mix, config["vocab_size"], r.seeds["traffic"])
    try:
        for prompt, _ in replay:                 # every shape, once
            eng.submit(prompt, max_new_tokens=2)
        eng.run_until_drained()
        cursor = 0

        def next_request():
            nonlocal cursor
            prompt, n = replay[cursor % len(replay)]
            cursor += 1
            return eng.submit(prompt, max_new_tokens=n)

        live = [next_request() for _ in range(mix["clients"])]
        while any(not eng.sessions[s].generated for s in live):
            eng.step_once()
        seen = {s: len(eng.sessions[s].generated) for s in live}
        stamps = {s: [] for s in live}           # (token index, time)
        finished = []
        with r.window():
            while True:
                with r.rec.span("tick"):
                    eng.step_once()
                now = time.perf_counter()
                for i, s in enumerate(live):
                    sess = eng.sessions[s]
                    for j in range(seen[s], len(sess.generated)):
                        stamps[s].append((j, now))
                    seen[s] = len(sess.generated)
                    if sess.done:
                        finished.append(s)
                        live[i] = next_request()
                        seen[live[i]] = 0
                        stamps[live[i]] = []
                if now - r.t_open >= r.seconds:
                    break
        window_s = r.t_close - r.t_open
        gaps, n_tokens, model_flops = [], 0, 0.0
        arch = common.arch(config)
        for s, st in stamps.items():
            S = len(eng.sessions[s].prompt)
            n_tokens += len(st)
            for j, _ in st:
                model_flops += (arch.prefill_flops(config, S) if j == 0
                                else arch.decode_flops(config, S + j - 1))
            gaps += [b[1] - a[1] for a, b in zip(st, st[1:])]
        r.values["serve_tokens_per_s"] = n_tokens / window_s
        r.values["itl_p95_ms"] = float(np.percentile(gaps, 95)) * 1e3
        r.values["tick_s"] = r.rec.durations("tick", r.t_open, r.t_close)
        r.values["prefill_s"] = r.rec.durations("prefill", r.t_open, r.t_close)
        r.values["serve_flops"] = model_flops
        r.attempted = len(finished) + len(live)
        r.log(f"{n_tokens} tokens, {len(finished)} requests finished, "
              f"{len(r.values['tick_s'])} ticks in {window_s:.6f} s; "
              f"itl samples: {len(gaps)}")
        rng = np.random.default_rng(r.seeds["traffic"] + 1)
        done = sorted(finished, key=lambda s: -len(eng.sessions[s].generated))
        sample = done[:1] + list(rng.permutation(done[1:]))
        picked, served = [], 0
        for s in sample:
            if served >= mix["check_tokens"] or len(picked) >= mix["check_requests"]:
                break
            sess = eng.sessions[s]
            picked.append((list(sess.prompt), list(sess.generated), sess.max_new))
            served += len(sess.generated)
    finally:
        eng.prefill_fn = prefill
        del eng
        gc.collect()

    r.values["picked"] = picked
    short = sum(len(g) != n for _, g, n in picked)
    r.check("short_streams", short, 0)
    # a run whose window finished too few requests has checked too little
    r.check("unchecked_tokens", max(0, mix["check_tokens"] - served), 0)
    r.check("served_gap", served_gap(config, r.seeds["weights"], picked,
                                     max_len), r.limits["served_gap"])
    r.log(f"checked {len(picked)} requests, {served} served tokens")


def served_gap(config, seed, picked, length, quant=False):
    """Widest gap, over the served tokens of ``picked``, between the
    reference's best logit and the served token's.  With ``quant`` the
    token compared at each position is the fp8 control's first choice."""
    if not picked:
        return float("inf")
    params = weights.make(config, seed)
    seqs = [p + g[:-1] for p, g, _ in picked]
    serve_logits = common.arch(config).serve_logits
    ref = serve_logits(config, params, seqs, length)
    ctl = (serve_logits(config, params, seqs, length, quant=True)
           if quant else None)
    worst = 0.0
    for i, (p, g, _) in enumerate(picked):
        rows = ref[i][len(p) - 1:]
        toks = (np.argmax(ctl[i][len(p) - 1:], axis=-1) if quant
                else np.asarray(g))
        gap = rows.max(axis=-1) - rows[np.arange(len(toks)), toks]
        worst = max(worst, float(gap.max()))
    return worst
