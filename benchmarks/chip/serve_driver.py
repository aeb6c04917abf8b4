"""The serving cells: the program's ``Server`` and continuous-batching
``Engine`` on one chip, driven by a traffic mix.

Set-up builds the server (weights from the seed), the engine, and warms
every shape the window can reach through the engine's own admission path:
a side batch of each row count the engine can pad to (1, 2, 4, ... and the
slot count) at the prompt bucket, spliced into slots and decoded.  The pool
holds every slot at full capacity, so no request can be preempted.

Two kinds of load, chosen by the mix:

- ``open_loop``: requests are due on a Poisson schedule and are submitted
  when due, whatever the engine is doing;
- ``backlog``: the queue is topped up before every step so that every slot
  is busy from the window's first step to its last.

Tokens are credited to the engine step that produced them, and a step's
time is read on the host once its sampled tokens are back.
"""

from __future__ import annotations

import gc
import time

import numpy as np

import bench_weights as bw
import harness
import traffic_gen


def build(fam, c: dict, mix: dict, seed: int):
    from repro.launch.mesh import make_host_communicator
    from repro.runtime.engine import Engine, EngineConfig
    from repro.runtime.server import Server, ServerConfig

    scfg = ServerConfig(
        max_batch=mix["slots"], max_new_tokens=mix["output_len"]["max"],
        temperature=0.0, seed=bw.weight_seed(seed),
    )
    with bw.program_weights(fam, c):
        server = Server(fam.program_config(c), harness.parallel_config(c), scfg,
                        make_host_communicator(1, 1))
    engine = Engine(server, EngineConfig(prompt_bucket=mix["prompt_bucket"],
                                         block_tokens=mix["block_tokens"]))
    return server, engine


def warm_rows(slots: int) -> list[int]:
    return sorted({1 << k for k in range(slots.bit_length()) if 1 << k <= slots} | {slots})


def warm(engine, vocab: int) -> list[int]:
    rng = np.random.default_rng(0)
    rows = warm_rows(engine.num_slots)
    for r in rows:
        for _ in range(r):
            engine.submit(rng.integers(1, vocab, size=engine.ecfg.prompt_bucket,
                                       dtype=np.int32), max_new=2)
        engine.run()
    return rows


def window(engine, mix: dict, reqs: list, seconds: float, traced: bool) -> dict:
    """Drive the engine for ``seconds``; returns per-request and per-step
    records on the host clock, relative to the window's start."""

    open_loop = mix["load"] == "open_loop"
    slots = engine.num_slots
    live: dict[int, dict] = {}      # rid -> record, until finished
    recs: list[dict] = []
    steps: list[dict] = []
    nxt = 0
    t0 = time.perf_counter()
    while True:
        el = time.perf_counter() - t0
        if el >= seconds:
            break
        with harness.span("bench.submit", traced):
            if open_loop:
                while nxt < len(reqs) and reqs[nxt]["due_s"] <= el:
                    q = reqs[nxt]
                    h = engine.submit(q["tokens"], max_new=q["max_new"])
                    rec = {"h": h, "due": q["due_s"], "sent": el, "times": []}
                    live[h.rid] = rec
                    recs.append(rec)
                    nxt += 1
            else:
                while len(engine.waiting) < slots and nxt < len(reqs):
                    q = reqs[nxt]
                    h = engine.submit(q["tokens"], max_new=q["max_new"])
                    rec = {"h": h, "due": el, "sent": el, "times": []}
                    live[h.rid] = rec
                    recs.append(rec)
                    nxt += 1
        if not engine.waiting and not any(r is not None for r in engine.active):
            if nxt >= len(reqs):
                raise RuntimeError("the mix ran out of requests inside the window")
            time.sleep(max(0.0, min(reqs[nxt]["due_s"] - el, seconds - el)))
            continue
        ts = time.perf_counter() - t0
        with harness.span("bench.step", traced):
            done = engine.step()
        te = time.perf_counter() - t0
        with harness.span("bench.record", traced):
            step = {"t0": ts, "t1": te, "prompts": [], "contexts": [], "tokens": 0}
            touched = [r for r in engine.active if r is not None] + done
            for h in touched:
                rec = live.get(h.rid)
                if rec is None:
                    continue
                new = len(h.generated) - len(rec["times"])
                if new <= 0:
                    continue
                if not rec["times"]:
                    step["prompts"].append(len(h.tokens))
                    new_decode = new - 1
                else:
                    new_decode = new
                if h.slot is not None:
                    rec["slot"] = h.slot
                for j in range(new_decode):
                    step["contexts"].append(len(h.tokens) + len(rec["times"]) + j
                                            + (0 if rec["times"] else 1))
                rec["times"].extend([te] * new)
                step["tokens"] += new
                if h.state == "finished":
                    rec["finished"] = te
                    del live[h.rid]
            steps.append(step)
    t1 = time.perf_counter() - t0
    due = sum(1 for q in reqs if q["due_s"] < t1) if open_loop else nxt
    return {"seconds": t1, "requests": recs, "steps": steps, "submitted": nxt,
            "due": due}


def drain(engine, recs: list, limit_s: float = 90.0) -> None:
    """Where the window finished no request (a short traced window), step
    the engine past its close, untimed and with nothing new submitted,
    until one finishes, so that the check has served requests to read."""

    t0 = time.perf_counter()
    while (not any(r["h"].state == "finished" for r in recs)
           and any(r is not None for r in engine.active)
           and time.perf_counter() - t0 < limit_s):
        engine.step()


def sample(recs: list, mix: dict, seed: int) -> list[dict]:
    """Requests finished, drawn from the seed: the one with the most served
    tokens first, then others until ``check_tokens``."""

    done = [r for r in recs if r["h"].state == "finished"]
    if not done:
        return []
    done.sort(key=lambda r: -len(r["h"].generated))
    rng = np.random.default_rng([int(seed) % 2**63, 7])
    picked, total = [done[0]], len(done[0]["h"].generated)
    for i in rng.permutation(np.arange(1, len(done))):
        if total >= mix["check_tokens"] or len(picked) >= mix["check_requests"]:
            break
        picked.append(done[i])
        total += len(done[i]["h"].generated)
    return [{"prompt": np.asarray(r["h"].tokens), "served": list(r["h"].generated)}
            for r in picked]


def last_step(engine, win: dict) -> dict | None:
    """The logits the window's last decode step produced, one row per
    request it advanced, with those requests' prompts and tokens."""

    if not win["steps"]:
        return None
    end = win["steps"][-1]["t1"]
    recs = [r for r in win["requests"]
            if len(r["times"]) >= 2 and r["times"][-1] == end and "slot" in r]
    if not recs or engine.logits is None:
        return None
    vocab = engine.server.cfg.vocab_size
    rows = np.asarray(engine.logits[:, -1, :vocab].astype(np.float32))
    return {
        "logits": rows[[r["slot"] for r in recs]],
        "requests": [{"prompt": np.asarray(r["h"].tokens), "served": list(r["h"].generated)}
                     for r in recs],
    }


def _teacher_forced(mix: dict, picked: list):
    bucket = mix["prompt_bucket"]
    T = bucket + mix["output_len"]["max"] - 1
    seqs = np.zeros((len(picked), T), np.int32)
    for i, p in enumerate(picked):
        seqs[i, bucket - len(p["prompt"]):bucket] = p["prompt"]
        seqs[i, bucket:bucket + len(p["served"]) - 1] = p["served"][:-1]
    return seqs


def reference_numbers(fam, c: dict, mix: dict, seed: int, picked: list,
                      last: dict | None, control: bool = False) -> dict:
    """The numbers the limits hold, from one teacher-forced pass of the
    family's reference over the sampled requests and the last decode
    step's: each prompt left-padded with zeros to the bucket (as the engine
    serves it), then its served tokens (all but the last).

    - ``max_gap``: the widest gap by which a served token's reference logit
      lies below the reference's best, over every served token sampled;
    - ``logit_err``: the last decode step's logits against the reference's
      at the same positions, over each row's largest reference logit.

    With ``control`` the float8 reference also runs over the same
    sequences, and the same numbers are read for it (``control_*``: the
    gaps of the tokens it ranks first, its logits), and for each served
    token altered to the next id (``altered_max_gap``)."""

    bucket = mix["prompt_bucket"]
    lasts = last["requests"] if last else []
    seqs = _teacher_forced(mix, picked + lasts)
    sides = [False, True] if control else [False]
    xs = {fp8: fam.hidden(seed, c, seqs, fp8=fp8) for fp8 in sides}
    out = {}
    if picked:
        rows, served = [], []
        for i, p in enumerate(picked):
            for j, t in enumerate(p["served"]):
                rows.append((i, bucket - 1 + j))
                served.append(t)
        rows = np.asarray(rows, np.int32)
        cand = {"served": np.asarray(served, np.int32)}
        if control:
            low = fam.score(seed, c, xs[True][rows[:, 0], rows[:, 1]], {}, fp8=True)
            cand["control"] = low["top"].astype(np.int32)
            nxt = cand["served"] + 1
            cand["altered"] = np.where(nxt < c["vocab_size"], nxt, 1).astype(np.int32)
        ref = fam.score(seed, c, xs[False][rows[:, 0], rows[:, 1]], cand)
        gaps = {k: ref["best"] - ref[k] for k in cand}
        out["max_gap"] = float(gaps["served"].max())
        out["_served_tokens"] = int(gaps["served"].size)
        if control:
            out["control_max_gap"] = float(gaps["control"].max())
            out["altered_max_gap"] = float(gaps["altered"].max())
    if lasts:
        rows = np.asarray([(len(picked) + i, bucket - 2 + len(p["served"]))
                           for i, p in enumerate(lasts)], np.int32)
        ref = np.asarray(fam.head(seed, c, xs[False][rows[:, 0], rows[:, 1]]))
        scale = np.abs(ref).max(-1)
        err = lambda got: float((np.abs(got - ref).max(-1) / scale).max())
        out["logit_err"] = err(last["logits"])
        out["_last_step_rows"] = len(lasts)
        if control:
            low = fam.head(seed, c, xs[True][rows[:, 0], rows[:, 1]], fp8=True)
            out["control_logit_err"] = err(np.asarray(low))
    return out


def run(spec: dict, seed: int, seconds: float, traced: bool, setup_t0: float,
        fault=None) -> dict:
    import jax

    c, mix = spec["config"], spec["traffic"]
    counters = harness.Counters()
    server, engine = build(spec["family"], c, mix, seed)
    rows = warm(engine, c["vocab_size"])
    if traced:
        seconds = min(seconds, mix["trace_seconds"])
    count = (int(np.ceil(mix["rate_per_s"] * seconds * 1.3)) + mix["block"]
             if mix["load"] == "open_loop" else int(seconds * mix["max_requests_per_s"]))
    reqs = traffic_gen.serve_requests(mix, seed, count, c["vocab_size"])
    if fault is not None:
        fault(server, engine)
    gc.collect()
    gc.freeze()
    before = counters.read()
    setup_s = time.perf_counter() - setup_t0
    trace_dir = None
    if traced:
        import tempfile

        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(trace_dir)
    with harness.span("bench.window", traced):
        win = window(engine, mix, reqs, seconds, traced)
    if traced:
        jax.profiler.stop_trace()
    gc.unfreeze()
    in_window = harness.Counters.diff(counters.read(), before)
    peak = harness.memory_peak(jax.devices()[:1])
    last = last_step(engine, win)
    drain(engine, win["requests"])
    picked = sample(win["requests"], mix, seed)
    del engine, server
    gc.collect()
    return {
        "setup_s": setup_s,
        "window": win,
        "warm_rows": rows,
        "in_window": in_window,
        "memory_peak_bytes": peak,
        "picked": picked,
        "last_step": last,
        "trace_dir": trace_dir,
    }


def end_to_end(res: dict, mix: dict) -> dict:
    """The cell's end-to-end numbers from the window's records."""

    win = res["window"]
    T = win["seconds"]
    recs = win["requests"]
    out = {}
    due = [r for r in recs if r["due"] < T]
    ttft = [(r["times"][0] - r["due"]) if r["times"] else float("inf") for r in due]
    ttft += [float("inf")] * (win["due"] - len(due))   # due, never submitted
    gaps = []
    for r in recs:
        t = [x for x in r["times"] if x <= T]
        gaps.extend(b - a for a, b in zip(t, t[1:]))
    generated = sum(s["tokens"] for s in win["steps"])
    prompts = sum(sum(s["prompts"]) for s in win["steps"])
    out["ttft_p90_s"] = harness.quantile(ttft, 0.90)
    out["itl_p98_s"] = harness.quantile(gaps, 0.98)
    out["itl_p99_s"] = harness.quantile(gaps, 0.99)
    out["output_tokens_per_s"] = generated / T
    out["serve_tokens_per_s"] = (generated + prompts) / T
    out["_counts"] = {
        "due": win["due"], "unserved": sum(1 for x in ttft if x == float("inf")),
        "gaps": len(gaps), "steps": len(win["steps"]),
        "late_max_s": max((r["sent"] - r["due"] for r in recs), default=0.0),
        "longest_step_s": max((s["t1"] - s["t0"] for s in win["steps"]), default=0.0),
    }
    return out
