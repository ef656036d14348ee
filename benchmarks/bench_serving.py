"""Serving economics: continuous cross-session batching vs per-session
batcher, and the paged-block KV pool vs per-slot rings.

The paper's cost argument (§4.2, §6) is that serverless serving only wins
when per-invocation cost is amortized across batched arrivals.  This section
drives the *same* request workload (``sessions`` concurrent clients, fixed
prompt/decode lengths) through

  * the old per-session batcher (one FIFO queue + its own event function per
    session — a model batch never mixes sessions),
  * the shared continuous-batching scheduler over per-slot rings (PR 2), and
  * the same scheduler over the shared paged-block KV pool with chunked
    prefill,

and reports req/invoke (batch occupancy), tokens/s (simulated), decode-slot
occupancy, $/1k tokens, and the KV memory footprint.  (The 1-device vs
8-device sharded comparison is ``repro.launch.sharded_smoke``, run on its
own with the device spoof set before JAX starts.)  A speculation cell
re-runs one request soup with draft-and-verify speculative decoding off vs
on (self-draft) and reports acceptance rate and target steps per emitted
token at asserted-identical outputs.  A second cell drives
the scheduler directly with one **long-prompt interloper** arriving into a
busy decode batch and measures per-step wall latency: a monolithic ring
admission stalls every slot for the full prefill, a chunked paged admission
bounds the stall at one ``prefill_chunk``.  Compute is billed under the
calibrated ``prefill``/``decode_step`` latency models (identical across
modes), so the cost comparison is deterministic; the real reduced model
still generates the tokens, and jits are pre-warmed so wall times reflect
steady state.

A fleet cell re-runs one request burst through the elastic scale-to-zero
scheduler fleet (disposable workers behind the shared dispatch queue,
parked journals + prefix-index blobs in the object store between bursts)
vs a solo resident scheduler, gates token-identical outputs, and
extrapolates the measured per-burst serverless bill (pay-per-invocation
worker starts + GB-seconds + S3 ops + S3 retention) across traffic
regimes against an always-on provisioned VM — the paper's §6 break-even
curve with the serving stack instead of ZooKeeper behind it.
"""

from __future__ import annotations

import time

from .common import save_artifact, table

PAGE_SIZE = 8
PREFILL_CHUNK = 8


def _drive_workload(cloud, frontend, cfg, *, n_requests, sessions, prompt_len,
                    max_new):
    from repro.launch.serve import spawn_workload

    spawn_workload(cloud, frontend, vocab=cfg.vocab, n_requests=n_requests,
                   sessions=sessions, prompt_len=prompt_len, max_new=max_new)
    t0 = time.time()
    cloud.run()
    return time.time() - t0


def _warm_steps(sched, params, chunk_lens):
    """Compile the scheduler's chunk and decode steps outside the billed
    clock.  Both donate the cache they are given, so they run on a copy and
    the scheduler's own cache stays live."""
    import jax
    import jax.numpy as jnp

    def spare():
        return jax.tree_util.tree_map(jnp.copy, sched.cache)

    for C in chunk_lens:
        sched._chunk(params, spare(), jnp.zeros((1, C), jnp.int32), 0)
    sched._decode(params, spare(), sched.last_tokens, sched.out_buf,
                  sched.out_pos, jnp.ones((sched.n_slots,), bool),
                  jax.random.key(0))


def _measure(mode, cfg, model, params, *, n_requests, sessions, prompt_len,
             max_new, batch_size):
    import jax.numpy as jnp
    import numpy as np

    from repro.core import SimCloud
    from repro.launch.serve import build_frontend

    front_mode, _, kv_mode = mode.partition(":")
    cloud = SimCloud(seed=0)
    frontend = build_frontend(cloud, cfg, model, params, mode=front_mode,
                              batch_size=batch_size, max_new=max_new,
                              prompt_len=prompt_len, kv_mode=kv_mode or "paged",
                              page_size=PAGE_SIZE, prefill_chunk=PREFILL_CHUNK)
    # pre-warm every jit shape the workload can hit, outside the billed clock
    if frontend.scheduler is not None:
        sched = frontend.scheduler
        chunks = ()
        if sched.kv_mode == "ring":
            sched._prefill(params, jnp.zeros((1, prompt_len), jnp.int32))
        else:
            chunks = {min(PREFILL_CHUNK, prompt_len),
                      prompt_len % PREFILL_CHUNK or PREFILL_CHUNK}
        _warm_steps(sched, params, chunks)
    else:
        for b in range(1, batch_size + 1):
            frontend.model_fn([np.zeros(prompt_len, np.int32)] * b)

    wall = _drive_workload(cloud, frontend, cfg, n_requests=n_requests,
                           sessions=sessions, prompt_len=prompt_len,
                           max_new=max_new)
    served = sum(len(v) for v in frontend.completions.values())
    stats = frontend.runtime.stats["serve"]
    # routing is an unbilled queue pipe, so total function invocations ==
    # model invocations; assert that stays true (the honest-accounting guard)
    total_inv = sum(st.invocations for st in frontend.runtime.stats.values())
    assert total_inv == stats.invocations, frontend.runtime.stats.keys()
    cost = frontend.runtime.cost_usd()
    tokens = served * max_new
    sstats = frontend.serving_stats()
    row = {
        "mode": mode,
        "served": f"{served}/{n_requests}",
        "invocations": stats.invocations,
        "req_per_invoke": round(served / stats.invocations, 2),
        "sim_s": round(cloud.now, 3),
        "tok_per_sim_s": round(tokens / cloud.now, 1),
        "cost_usd": round(cost, 8),
        "usd_per_1k_tok": round(1000.0 * cost / tokens, 8),
        "occupancy": (round(frontend.scheduler.occupancy(), 2)
                      if frontend.scheduler is not None else ""),
        "kv_kib": (round(sstats["kv_pool_bytes"] / 1024, 1)
                   if "kv_pool_bytes" in sstats else ""),
        "kv_hw_kib": (round(sstats["kv_high_water_bytes"] / 1024, 1)
                      if "kv_high_water_bytes" in sstats else ""),
        "dropped": frontend.dropped_requests(),
        "wall_s": round(wall, 1),
    }
    assert served == n_requests, f"{mode}: served {served}/{n_requests}"
    return row


INTERLOPER_AT = 4       # steady-state steps before the long prompt arrives
STALL_WINDOW = 18       # steps measured from its arrival (covers admission)


def _interloper_cell(cfg, model, params, *, kv_mode, n_slots=4, short_len=16,
                     long_len=512, max_new=20, prefill_chunk=32):
    """Per-step wall latency under a long-prompt admission mid-decode.

    Short requests keep the batch busy; at step ``INTERLOPER_AT`` a
    ``long_len``-token prompt arrives.  Ring mode prefills it monolithically
    inside admission — every other slot stalls for the whole prompt in one
    step; paged mode lands one ``prefill_chunk`` per step, bounding each
    step's stall at a chunk.  The headline number is p95/max over the
    ``STALL_WINDOW`` steps from the arrival (a whole-run p95 would mostly
    average steady-state steps and hide a rare 100 ms stall).  Also returns
    the KV memory numbers at equal occupancy.
    """
    import jax
    import numpy as np

    from repro.serve.scheduler import DecodeScheduler

    sched = DecodeScheduler(model, params, n_slots=n_slots,
                            max_seq=long_len + max_new, kv_mode=kv_mode,
                            page_size=PAGE_SIZE, prefill_chunk=prefill_chunk)
    rng = np.random.default_rng(0)

    def scenario():
        samples = []
        rid = [0]

        def submit(length, max_tokens):
            sched.submit(f"s{rid[0]}", f"r{rid[0]}",
                         rng.integers(0, cfg.vocab, size=length).astype(np.int32),
                         max_tokens)
            rid[0] += 1

        for _ in range(n_slots):
            submit(short_len, max_new)
        step = 0
        while sched.busy():
            t0 = time.time()
            if step == INTERLOPER_AT:      # the long-prompt interloper
                submit(long_len, max_new)
            sched.step()
            jax.block_until_ready(sched.out_pos)
            samples.append((time.time() - t0) * 1000.0)
            step += 1
            if step < 30 and not sched.busy():
                submit(short_len, max_new)  # keep occupancy up
            assert step < 500
        return samples

    scenario()                              # warm every jit shape
    sched.reset()
    rng = np.random.default_rng(0)
    samples = scenario()
    mem = sched.kv_memory_stats()
    arr = np.asarray(samples)
    window = arr[INTERLOPER_AT:INTERLOPER_AT + STALL_WINDOW]
    return {
        "kv_mode": kv_mode,
        "steps": len(samples),
        "p50_step_ms": round(float(np.percentile(arr, 50)), 2),
        "stall_p95_ms": round(float(np.percentile(window, 95)), 2),
        "stall_max_ms": round(float(window.max()), 2),
        "occupancy": round(sched.occupancy(), 2),
        "kv_pool_kib": round(mem["kv_pool_bytes"] / 1024, 1),
        "kv_high_water_kib": round(mem["kv_high_water_bytes"] / 1024, 1),
        **({"kv_pages_high_water": mem["kv_pages_high_water"],
            "kv_pages": mem["kv_pages"]} if kv_mode == "paged" else {}),
    }


N_IDLE = 3              # mostly-idle long-runner sessions
HOT_REQUESTS = 5        # back-to-back short requests from the hot session


def _idle_session_cell(cfg, model, params, *, offload, page_size=8,
                       idle_prompt=16, idle_new=40, hot_prompt=8, hot_new=4,
                       prefill_chunk=8):
    """N mostly-idle long-runner sessions + 1 hot session, pool sized so the
    idle sessions pin it entirely (FaaSKeeper's anti-pattern: capacity held
    by compute that isn't earning it).  Without offload every hot request
    stalls in the pending queue until an idle session *completes*; with
    offload the pressure policy evicts the longest-resident idle slot to the
    object store and the hot request admits immediately, paying storage ops
    instead of stall steps.  Reported per mode: hot-session admission-stall
    p95/total (in scheduler steps — deterministic), mean pool occupancy, and
    the itemized storage bill.  Equal pool size across modes.
    """
    import numpy as np

    from repro.core.cost import page_blob_cost
    from repro.serve.scheduler import DecodeScheduler

    idle_need = -(-(idle_prompt + idle_new - 1) // page_size)
    hot_need = -(-(hot_prompt + hot_new - 1) // page_size)
    kv_pages = N_IDLE * idle_need + hot_need - 1   # hot is always pool-gated
    sched = DecodeScheduler(model, params, n_slots=N_IDLE + 1,
                            max_seq=idle_prompt + idle_new,
                            page_size=page_size, prefill_chunk=prefill_chunk,
                            kv_pages=kv_pages, offload=offload)
    rng = np.random.default_rng(0)
    for k in range(N_IDLE):
        sched.submit(f"idle{k}", f"r{k}",
                     rng.integers(0, cfg.vocab, size=idle_prompt).astype(np.int32),
                     idle_new)
    stalls, hot_done, hot_out, rid = [], 0, False, N_IDLE
    steps = 0
    while sched.busy() or hot_done < HOT_REQUESTS:
        if not hot_out and hot_done < HOT_REQUESTS:
            sched.submit("hot", f"r{rid}",
                         rng.integers(0, cfg.vocab,
                                      size=hot_prompt).astype(np.int32),
                         hot_new)
            rid += 1
            hot_out = True
        for fin in sched.step():
            if fin.session == "hot":
                stalls.append(fin.admitted_step - fin.submitted_step)
                hot_done += 1
                hot_out = False
        steps += 1
        assert steps < 2000, "idle-session cell failed to drain"
    ost = sched.offload_stats()
    storage_usd = page_blob_cost(ost["offload_puts"], ost["offload_gets"])
    return {
        "offload": offload,
        "kv_pages": kv_pages,
        "steps": steps,
        "hot_served": hot_done,
        "hot_stall_total_steps": int(np.sum(stalls)),
        "hot_stall_p95_steps": round(float(np.percentile(stalls, 95)), 1),
        "hot_stall_max_steps": int(np.max(stalls)),
        "pool_occupancy": round(sched.pool_occupancy(), 3),
        "preemptions": ost["preemptions"],
        "restores": ost["restores"],
        "offload_kib": round(ost["offload_bytes"] / 1024, 1),
        "restore_kib": round(ost["restore_bytes"] / 1024, 1),
        "storage_ops": ost["offload_puts"] + ost["offload_gets"],
        "storage_usd": round(storage_usd, 8),
    }


MT_SESSIONS = 4         # concurrent chat sessions
MT_TURNS = 3            # turns per session (turn >= 2 extends the history)


def _multiturn_cell(cfg, model, params, *, sharing, page_size=8, sys_len=16,
                    user_len=6, max_new=6, prefill_chunk=8, max_seq=96,
                    kv_pages=24):
    """Multi-turn chat with a shared system prompt: every session's prompt
    starts with the same ``sys_len`` tokens, and each turn's prompt is the
    full conversation so far plus ``user_len`` new tokens.  With
    sharing+parking off the scheduler re-prefills the whole conversation
    every turn; with them on, turn 1 shares the system-prompt pages across
    sessions (prefix index) and turn >= 2 restores the session's parked
    journal and prefills only the new tail — marginal tokens only.  Equal
    pool size across modes; outputs must be identical (the parity guard).
    Reported: prefill tokens on turn-1 vs later turns, KV pool high-water,
    CoW/park traffic, and the parked-retention storage bill.
    """
    import numpy as np

    from repro.core.cost import page_blob_cost
    from repro.serve.scheduler import DecodeScheduler

    # pool sized to fit the concurrent active worst case but NOT four idle
    # journals on top: parked retention must earn its keep by offloading
    # under pressure (that is the storage-$ half of the trade)
    sched = DecodeScheduler(model, params, n_slots=MT_SESSIONS,
                            max_seq=max_seq, page_size=page_size,
                            prefill_chunk=prefill_chunk, kv_pages=kv_pages,
                            prefix_sharing=sharing, park_sessions=sharing)
    # one RNG per session: user turns are a function of (session, turn), not
    # of cross-session completion order, so the off/on prompts — and hence
    # outputs — are comparable request-for-request
    rng = np.random.default_rng(0)
    rngs = {f"c{i}": np.random.default_rng(100 + i)
            for i in range(MT_SESSIONS)}
    sys_prompt = rng.integers(0, cfg.vocab, size=sys_len).astype(np.int32)
    hist = {s: np.concatenate(
        [sys_prompt, r.integers(0, cfg.vocab, size=user_len).astype(np.int32)])
        for s, r in rngs.items()}
    turn = {s: 0 for s in hist}
    prefill_by_turn = [0] * MT_TURNS
    outputs = {}
    # arrivals trickle in: the first session's turn-1 publishes the system
    # prompt's pages, so later sessions' turn-1 index-hits them
    sessions = list(hist)
    sched.submit(sessions[0], f"{sessions[0]}t0", hist[sessions[0]], max_new)
    steps = 0
    done = 0
    while sched.busy() or done < MT_SESSIONS * MT_TURNS:
        for fin in sched.step():
            if fin.request_id == f"{sessions[0]}t0":
                for s in sessions[1:]:
                    sched.submit(s, f"{s}t0", hist[s], max_new)
            s, t = fin.session, turn[fin.session]
            outputs[fin.request_id] = np.asarray(fin.tokens)
            prefill_by_turn[t] += len(hist[s]) - fin.reused_tokens
            done += 1
            turn[s] += 1
            if turn[s] < MT_TURNS:
                hist[s] = np.concatenate(
                    [hist[s], np.asarray(fin.tokens, np.int32),
                     rngs[s].integers(0, cfg.vocab,
                                      size=user_len).astype(np.int32)])
                sched.submit(s, f"{s}t{turn[s]}", hist[s], max_new)
        steps += 1
        assert steps < 3000, "multi-turn cell failed to drain"
    mem = sched.kv_memory_stats()
    sh = sched.sharing_stats()
    # put/get op charges for park offloads/restores; retention GB-time is a
    # frontend-level meter (needs the sim clock) and is billed there
    storage_ops_usd = page_blob_cost(sched.blob_store.puts,
                                     sched.blob_store.gets)
    return {
        "sharing": sharing,
        "steps": steps,
        "prefill_turn1": prefill_by_turn[0],
        "prefill_later_turns": sum(prefill_by_turn[1:]),
        "prefill_tokens_total": sched.prefill_tokens,
        "shared_prefix_tokens": sh["shared_prefix_tokens"],
        "park_hits": sh["park_hits"],
        "index_hits": sh["index_hits"],
        "cow_splits": sh["cow_splits"],
        "kv_pages_high_water": mem["kv_pages_high_water"],
        "kv_high_water_kib": round(mem["kv_high_water_bytes"] / 1024, 1),
        "park_storage_ops_usd": round(storage_ops_usd, 9),
        "outputs": {k: v.tolist() for k, v in outputs.items()},
    }


SPEC_K = 3              # draft tokens proposed per verify round
SPEC_REQUESTS = 8
SPEC_SESSIONS = 4


def _speculation_cell(cfg, model, params, *, spec, page_size=8, prompt_len=12,
                      max_new=10, prefill_chunk=8, n_slots=4, max_seq=32):
    """Draft-and-verify speculative decoding, off vs on (self-draft).

    The same request soup runs through the scheduler with speculation off
    (one decode step per token) and on (the draft proposes ``SPEC_K`` tokens
    per slot, the target verifies them in one chunked step over the shared
    paged pool, rejections roll back through the CoW/free-list machinery).
    Outputs must be identical — acceptance only buys *speed*, never changes
    a token (every emitted token is the target's own greedy argmax over a
    canonical prefix).  Reported: scheduler steps, verify rounds, acceptance
    rate, and target steps per emitted token (1.0 = no speedup,
    1/(k+1) = every proposal accepted).  Self-draft acceptance is high but
    not 1.0: the draft runs its own ring cache with its own chunk
    boundaries, so low-bit drift occasionally flips an argmax — exactly the
    disagreement the verify step is there to absorb.
    """
    import numpy as np

    from repro.serve.scheduler import DecodeScheduler

    kw = (dict(draft_model=model, draft_params=params, spec_k=SPEC_K)
          if spec else {})
    sched = DecodeScheduler(model, params, n_slots=n_slots, max_seq=max_seq,
                            page_size=page_size, prefill_chunk=prefill_chunk,
                            **kw)
    rng = np.random.default_rng(0)
    for i in range(SPEC_REQUESTS):
        sched.submit(f"c{i % SPEC_SESSIONS}", f"r{i}",
                     rng.integers(0, cfg.vocab,
                                  size=prompt_len).astype(np.int32),
                     max_new)
    outputs = {}
    steps = 0
    while sched.busy():
        for fin in sched.step():
            outputs[fin.request_id] = np.asarray(fin.tokens).tolist()
        steps += 1
        assert steps < 2000, "speculation cell failed to drain"
    emitted = SPEC_REQUESTS * max_new
    row = {
        "speculation": spec,
        "steps": steps,
        "tokens": emitted,
        "steps_per_token": round(steps / emitted, 3),
        "outputs": outputs,
    }
    if spec:
        ss = sched.spec_stats()
        row.update({
            "spec_k": ss["spec_k"],
            "verify_rounds": ss["spec_rounds"],
            "acceptance_rate": round(ss["spec_acceptance_rate"], 3),
            "target_steps_per_token": round(ss["spec_steps_per_token"], 3),
        })
    return row


FLEET_WORKERS = 2       # fleet ceiling (scale-to-zero floor is 0)
FLEET_REQUESTS = 12     # one burst
FLEET_SESSIONS = 4
FLEET_SLOTS = 4         # decode slots per worker
# traffic regimes for the break-even curve, in request bursts per day
FLEET_REGIMES = (("infrequent", 4), ("diurnal", 96), ("bursty", 1440))


def _fleet_cost_cell(cfg, model, params, *, prompt_len=16, max_new=8):
    """Serverless scheduler fleet vs always-on provisioned baseline.

    The same burst runs through (a) the elastic fleet — workers spawn on
    the burst, drain-and-park to the blob store when the queue empties,
    scale to zero — and (b) a solo resident scheduler; outputs must be
    token-identical (the parity guard the differential harness proves in
    depth).  The fleet run is billed FaaSKeeper-style: per-invocation
    worker starts + cold-start latency, GB-seconds while decoding, Table-4
    S3 op charges for the park/journal traffic, and S3 retention on the
    parked bytes.  The measured per-burst bill then extrapolates across
    ``FLEET_REGIMES`` against an always-on t3.medium (§6 deployment
    constants): daily serverless cost = bursts/day x burst bill + a full
    day of retention on the parked state; the provisioned baseline pays
    the VM whether requests arrive or not.
    """
    import numpy as np

    from repro.core import SimCloud
    from repro.core.cost import VM_DAILY, page_blob_retention_cost
    from repro.launch.serve import build_frontend, spawn_workload

    def _serve(fleet_n):
        cloud = SimCloud(seed=0)
        fe = build_frontend(cloud, cfg, model, params, mode="continuous",
                            batch_size=FLEET_SLOTS, max_new=max_new,
                            prompt_len=prompt_len, kv_mode="paged",
                            page_size=PAGE_SIZE, prefill_chunk=PREFILL_CHUNK,
                            fleet_size=fleet_n,
                            scale_to_zero=bool(fleet_n))
        scheds = (fe.fleet._all_scheds() if fe.fleet is not None
                  else [fe.scheduler])
        for sched in scheds:            # pre-warm outside the billed clock
            _warm_steps(sched, params, [min(PREFILL_CHUNK, prompt_len)])
        spawn_workload(cloud, fe, vocab=cfg.vocab, n_requests=FLEET_REQUESTS,
                       sessions=FLEET_SESSIONS, prompt_len=prompt_len,
                       max_new=max_new)
        cloud.run()
        served = sum(len(v) for v in fe.completions.values())
        assert served == FLEET_REQUESTS, \
            f"fleet cell served {served}/{FLEET_REQUESTS}"
        outs = {s: [np.asarray(t).tolist() for t in v]
                for s, v in fe.results.items()}
        return fe, outs

    fleet_fe, fleet_out = _serve(FLEET_WORKERS)
    solo_fe, solo_out = _serve(0)
    s = fleet_fe.serving_stats()
    burst_usd = (s["cost_usd"] + s["offload_storage_usd"]
                 + s["park_storage_usd"])
    parked_bytes = fleet_fe.fleet.blob_store.bytes_stored
    retention_day = page_blob_retention_cost(parked_bytes * 86400.0)
    provisioned_day = VM_DAILY["t3.medium"]
    regimes = []
    for name, bursts in FLEET_REGIMES:
        serverless = bursts * burst_usd + retention_day
        regimes.append({
            "regime": name, "bursts_per_day": bursts,
            "serverless_usd_day": round(serverless, 6),
            "provisioned_usd_day": round(provisioned_day, 4),
            "savings_factor": round(provisioned_day / serverless, 1),
        })
    return {
        "workers_max": FLEET_WORKERS,
        "requests_per_burst": FLEET_REQUESTS,
        "identical_outputs": fleet_out == solo_out,
        "scaled_to_zero": s["workers_live"] == 0,
        "spawns": s["spawns"],
        "retires": s["retires"],
        "cold_starts_from_zero": s["cold_starts_from_zero"],
        "worker_invocations": s["worker_invocations"],
        "meta_puts": s["meta_puts"],
        "index_journal_puts": s["index_journal_puts"],
        "burst_usd": round(burst_usd, 8),
        "worker_usd": round(s["worker_cost_usd"], 8),
        "storage_ops_usd": round(s["offload_storage_usd"], 8),
        "parked_kib": round(parked_bytes / 1024, 1),
        "retention_usd_day": round(retention_day, 9),
        "provisioned_usd_day": provisioned_day,
        "break_even_bursts_per_day": round(
            (provisioned_day - retention_day) / max(burst_usd, 1e-12), 1),
        "regimes": regimes,
    }


def run(n: int = 32, arch: str = "minicpm-2b", sessions: int = 8,
        prompt_len: int = 16, max_new: int = 8, batch_size: int = 8):
    import jax

    from repro import configs
    from repro.models import build_model

    cfg = configs.get(arch).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))

    rows = []
    for mode in ("per-session", "continuous:ring", "continuous:paged"):
        rows.append(_measure(mode, cfg, model, params, n_requests=n,
                             sessions=sessions, prompt_len=prompt_len,
                             max_new=max_new, batch_size=batch_size))

    base, ring, paged = rows
    print(table(
        f"serving: {arch} x {n} requests / {sessions} sessions "
        f"(prompt {prompt_len}, decode {max_new}, width {batch_size})",
        rows, ["mode", "served", "invocations", "req_per_invoke", "sim_s",
               "tok_per_sim_s", "cost_usd", "usd_per_1k_tok", "occupancy",
               "kv_kib", "kv_hw_kib", "dropped"]))

    inter = [_interloper_cell(cfg, model, params, kv_mode=m)
             for m in ("ring", "paged")]
    print(table(
        "long-prompt interloper: step wall latency over the "
        f"{STALL_WINDOW}-step admission window (monolithic vs chunked "
        "prefill) and KV memory at equal occupancy",
        inter, ["kv_mode", "steps", "p50_step_ms", "stall_p95_ms",
                "stall_max_ms", "occupancy", "kv_pool_kib",
                "kv_high_water_kib"]))

    idle = [_idle_session_cell(cfg, model, params, offload=o)
            for o in (False, True)]
    print(table(
        f"idle sessions: {N_IDLE} long-runner sessions pin the pool while a "
        f"hot session submits {HOT_REQUESTS} short requests — admission "
        "stall with storage-backed preemption off vs on (equal pool size)",
        idle, ["offload", "kv_pages", "steps", "hot_stall_total_steps",
               "hot_stall_p95_steps", "hot_stall_max_steps", "pool_occupancy",
               "preemptions", "restores", "offload_kib", "restore_kib",
               "storage_usd"]))

    mt = [_multiturn_cell(cfg, model, params, sharing=s)
          for s in (False, True)]
    mt_off, mt_on = mt
    # parity guard: sharing must change the bill, never the tokens
    assert mt_off["outputs"] == mt_on["outputs"], \
        "prefix sharing / parking changed the generated tokens"
    for row in mt:
        row.pop("outputs")
    print(table(
        f"multi-turn chat: {MT_SESSIONS} sessions x {MT_TURNS} turns over a "
        "shared system prompt — prefill paid per turn with prefix sharing + "
        "session parking off vs on (equal pool size, identical outputs)",
        mt, ["sharing", "steps", "prefill_turn1", "prefill_later_turns",
             "prefill_tokens_total", "shared_prefix_tokens", "park_hits",
             "index_hits", "cow_splits", "kv_pages_high_water",
             "kv_high_water_kib", "park_storage_ops_usd"]))

    sp = [_speculation_cell(cfg, model, params, spec=s)
          for s in (False, True)]
    sp_off, sp_on = sp
    # the speculation invariant: acceptance buys speed, never tokens
    assert sp_off["outputs"] == sp_on["outputs"], \
        "speculative decoding changed the generated tokens"
    for row in sp:
        row.pop("outputs")
    print(table(
        f"speculative decoding: {SPEC_REQUESTS} requests / {SPEC_SESSIONS} "
        f"sessions, self-draft k={SPEC_K} — scheduler steps per emitted "
        "token with draft-and-verify off vs on (identical outputs)",
        sp, ["speculation", "steps", "tokens", "steps_per_token",
             "verify_rounds", "acceptance_rate", "target_steps_per_token"]))

    fc = _fleet_cost_cell(cfg, model, params, prompt_len=prompt_len,
                          max_new=max_new)
    # the fleet parity guard: elasticity changes the bill, never the tokens
    assert fc["identical_outputs"], \
        "fleet serving changed the generated tokens vs the resident scheduler"
    print(table(
        f"elastic fleet: one {FLEET_REQUESTS}-request burst through a "
        f"scale-to-zero fleet (max {FLEET_WORKERS} workers) vs an always-on "
        "t3.medium — measured per-burst bill extrapolated across traffic "
        "regimes (identical outputs vs the resident scheduler)",
        fc["regimes"], ["regime", "bursts_per_day", "serverless_usd_day",
                        "provisioned_usd_day", "savings_factor"]))
    print(f"fleet burst ${fc['burst_usd']:.6f} ({fc['worker_invocations']} "
          f"worker invocations ${fc['worker_usd']:.6f}, storage ops "
          f"${fc['storage_ops_usd']:.6f}); {fc['parked_kib']} KiB parked "
          f"between bursts at ${fc['retention_usd_day']:.9f}/day retention; "
          f"break-even at {fc['break_even_bursts_per_day']} bursts/day")

    i_off, i_on = idle
    stall_freed = 1.0 - (i_on["hot_stall_total_steps"]
                         / max(i_off["hot_stall_total_steps"], 1))
    i_ring, i_paged = inter
    summary = {
        "arch": arch, "requests": n, "sessions": sessions,
        "prompt_len": prompt_len, "max_new": max_new, "batch_size": batch_size,
        "page_size": PAGE_SIZE, "prefill_chunk": PREFILL_CHUNK,
        "rows": rows,
        "interloper": inter,
        "invocation_reduction": round(
            base["invocations"] / paged["invocations"], 2),
        "cost_reduction": round(base["cost_usd"] / paged["cost_usd"], 2),
        "cross_session_batching": paged["req_per_invoke"] > 1.0,
        "fewer_invocations_than_baseline":
            paged["invocations"] < base["invocations"],
        # the two levers the paged rewrite is for: live-token KV memory and
        # chunk-bounded admission stalls
        "paged_kv_below_ring":
            i_paged["kv_high_water_kib"] < i_ring["kv_high_water_kib"],
        "paged_kv_reduction": round(
            i_ring["kv_high_water_kib"] / max(i_paged["kv_high_water_kib"], 1e-9), 2),
        "paged_stall_p95_below_ring":
            i_paged["stall_p95_ms"] < i_ring["stall_p95_ms"],
        "interloper_stall_reduction": round(
            i_ring["stall_p95_ms"] / max(i_paged["stall_p95_ms"], 1e-9), 2),
        "interloper_max_stall_reduction": round(
            i_ring["stall_max_ms"] / max(i_paged["stall_max_ms"], 1e-9), 2),
        # storage-backed preemption: the pay-as-you-go tradeoff — hot-session
        # admission stalls freed vs the itemized storage bill (offload cells
        # carry storage_usd / offload_kib / restore_kib per mode)
        "idle_session": {"offload_off": i_off, "offload_on": i_on},
        "offload_stall_freed_frac": round(stall_freed, 3),
        "offload_frees_half_the_stalls": stall_freed >= 0.5,
        # prefix sharing + session parking: multi-turn workloads pay for
        # marginal tokens only — the turn >= 2 prefill reduction at equal
        # pool size with identical outputs, and the retention bill
        "multi_turn": {"sharing_off": mt_off, "sharing_on": mt_on},
        "multiturn_prefill_reduction": round(
            mt_off["prefill_later_turns"]
            / max(mt_on["prefill_later_turns"], 1), 2),
        "multiturn_prefill_halved": (
            mt_on["prefill_later_turns"]
            * 2 <= mt_off["prefill_later_turns"]),
        "multiturn_outputs_identical": True,   # asserted above
        # draft-and-verify speculation: steps-per-token off vs on at
        # identical outputs — the draft's cost rides in extra dispatches per
        # round, the win is fewer target decode steps per emitted token
        "speculation": {"spec_off": sp_off, "spec_on": sp_on},
        "spec_acceptance_rate": sp_on["acceptance_rate"],
        "spec_steps_per_token": sp_on["target_steps_per_token"],
        "spec_step_reduction": round(sp_off["steps"] / sp_on["steps"], 2),
        "spec_fewer_steps_than_baseline": sp_on["steps"] < sp_off["steps"],
        "spec_outputs_identical": True,        # asserted above
        # elastic scale-to-zero fleet: pay-per-invocation + retention vs the
        # always-on VM — cheaper whenever traffic is bursty enough to idle,
        # at token-identical outputs (asserted above)
        "fleet": fc,
        "fleet_identical_outputs": fc["identical_outputs"],
        "fleet_scaled_to_zero": fc["scaled_to_zero"],
        "fleet_savings_factor_infrequent": fc["regimes"][0]["savings_factor"],
        "fleet_cheaper_at_low_traffic":
            fc["regimes"][0]["savings_factor"] > 1.0,
    }
    print(f"\ncontinuous(paged) vs per-session: "
          f"{summary['invocation_reduction']}x fewer invocations, "
          f"{summary['cost_reduction']}x cheaper; paged vs ring: "
          f"{summary['paged_kv_reduction']}x less KV high-water, "
          f"{summary['interloper_stall_reduction']}x lower p95 step stall "
          f"while a long prompt is admitted; offload frees "
          f"{100 * summary['offload_stall_freed_frac']:.0f}% of hot-session "
          f"admission-stall steps for ${i_on['storage_usd']:.6f} of storage ops; "
          f"prefix sharing + parking cut turn>=2 prefill "
          f"{summary['multiturn_prefill_reduction']}x with identical outputs; "
          f"speculation (self-draft k={SPEC_K}) cuts scheduler steps "
          f"{summary['spec_step_reduction']}x at "
          f"{summary['spec_acceptance_rate']:.2f} acceptance, "
          f"identical outputs; scale-to-zero fleet at infrequent traffic is "
          f"{summary['fleet_savings_factor_infrequent']}x cheaper than "
          f"always-on, identical outputs")
    assert summary["paged_kv_below_ring"], (i_ring, i_paged)
    assert summary["offload_frees_half_the_stalls"], (i_off, i_on)
    assert summary["multiturn_prefill_halved"], (mt_off, mt_on)
    assert summary["spec_fewer_steps_than_baseline"], (sp_off, sp_on)
    assert summary["spec_steps_per_token"] <= 0.75, sp_on
    assert summary["fleet_scaled_to_zero"], fc
    assert summary["fleet_cheaper_at_low_traffic"], fc
    save_artifact("BENCH_serving", summary)
    return summary


if __name__ == "__main__":
    run()
