"""Serving throughput: continuous-batching scheduler vs. the seed's
sequential per-client loop, dense vs. block-paged KV layouts, and the
async cloud channel vs. the blocking dispatch.

Measures aggregate decode tokens/s on the tiny trained EE model for slot
counts 1/4/8/16 against the sequential baseline (same request set), in
co-inference mode at θ=0.8.  The acceptance bar for the batching PR is
>= 3x aggregate tokens/s at 8 slots.  ``--kv-layout paged`` (or ``both``)
additionally reports tokens/s, pooled-KV bytes, achieved decode KV HBM
bytes/token and the achieved-vs-roofline HBM fraction per layout at 8/16
slots (see docs/kv_paging.md); ``--kv-dtype int8`` adds the quantized
page pool, and with ``--check`` asserts the int8 pool cuts decode KV HBM
bytes/token >= 1.8x vs float32 at 8 slots, that paged float32 stays
token-identical to dense, and that the int8 exit-rate drift is bounded.
Every sweep row is also written to ``--json`` (BENCH_throughput.json).

``--channel sim`` runs the async-transport comparison instead
(docs/async_transport.md): the same WiFi-class ``AsyncSimChannel`` priced
in virtual time, dispatched blocking vs. overlapped at 8 slots, plus a
deadline-miss trace (replies slower than the deadline -> edge-committed
tokens instead of stalls).  With ``--check`` it asserts the overlapped
virtual makespan beats the blocking one and that the deadline trace
still completes every stream.

``--spec-k K`` runs the drafting sweep instead: the classic 1-token
speculative path vs. K-token edge drafts, both overlapped at 8 slots on a
high-RTT WAN-class channel with a per-request cloud service point.  Each
verification request pays RTT and serializes through the service point,
so shipping k provisional tokens per request cuts the wire/service tax
~k-fold while the accept-prefix/rewind reconcile keeps streams greedy
token-identical to the blocking run.  With ``--check`` it asserts the
K-token sweep beats spec_k=1 on virtual makespan and that the acceptance
rate is measured; per-k rows (incl. ``accept_rate``) land in ``--json``.

``--prefix-share`` runs the radix prefix-sharing sweep instead
(docs/kv_paging.md §Prefix sharing): N streams whose prompts share a
common system prefix, admitted via chunked prefill on the paged pool
with and without ``prefix_share``, on float32 and int8 pools.  Sharing
maps refcounted prefix pages into every stream's block table (skipping
their prefill chunks and hidden-state uploads) and copy-on-writes the
partial tail page on first divergence; exact-duplicate prompts hit a
cached terminal.  With ``--check`` it asserts fewer prefill chunks,
fewer page allocations, fewer uploaded bytes, >=1 CoW copy and
token-identical streams for both dtypes, plus an all-terminal second
wave of re-sent prompts.

``--cloud-batch`` runs the multi-client sweep instead: ``--clients N``
edge engines (one slot + one WiFi link each) share one cloud, and the
shared ``CloudBatcher`` (one masked cloud step per wave of concurrent
requests, priced by a batching ``CloudServicePoint``) is compared against
the per-request FIFO cloud.  With ``--check`` it asserts the batched
cloud virtual makespan beats FIFO at N>=4 and that both variants emit
token-identical streams to N independent sync runs.

    PYTHONPATH=src:. python benchmarks/throughput_bench.py [--check]
    PYTHONPATH=src:. python benchmarks/throughput_bench.py --kv-layout both
    PYTHONPATH=src:. python benchmarks/throughput_bench.py --channel sim --check
    PYTHONPATH=src:. python benchmarks/throughput_bench.py --clients 4 --cloud-batch --check
    PYTHONPATH=src:. python benchmarks/throughput_bench.py --spec-k 4 --check
    PYTHONPATH=src:. python benchmarks/throughput_bench.py --prefix-share --check
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from repro.core.collm import CollmConfig
from repro.core.netsim import NetworkParams
from repro.core.transport import (AsyncSimChannel, CloudServicePoint,
                                  ScriptedChannel)
from repro.launch.compile_cache import enable_compile_cache
from repro.roofline.analyze import (decode_kv_bytes_per_token,
                                    hbm_roofline_fraction)
from repro.serving.engine import ServingSystem

from benchmarks.common import PAPER_NET, tiny_trained_model

SLOT_COUNTS = (1, 4, 8, 16)


def _requests(data, n_clients: int, prompt_len: int = 12):
    return [data.sample_tokens(prompt_len) for _ in range(n_clients)]


def _tokens_per_s(fn, total_tokens: int, repeats: int) -> float:
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return total_tokens / best


def run(csv: bool = False, *, n_clients: int = 16, max_new: int = 24,
        theta: float = 0.8, repeats: int = 1, check: bool = False,
        rows: list = None) -> dict:
    tiny = tiny_trained_model()
    model, params, data = tiny["model"], tiny["params"], tiny["data"]
    prompts = _requests(data, n_clients)
    total = n_clients * max_new
    mean_ctx = float(np.mean([len(p) for p in prompts])) + max_new / 2.0
    ccfg = CollmConfig(theta=theta)

    # both engines are warmed with the SAME shapes they are measured at
    # (same max_new -> same max_seq -> same compiled graphs) and timed with
    # the same repeat count.  Note the sequential path re-traces its edge
    # step per client by construction (fresh EdgeClient jit wrapper), which
    # no warmup can amortize — that cost is intrinsic to the seed loop.
    seq_sys = ServingSystem(model, params, ccfg)
    seq_sys.generate_sequential(prompts[:2], max_new)       # warm compile
    seq_tps = _tokens_per_s(
        lambda: seq_sys.generate_sequential(prompts, max_new, mode="collm"),
        total, repeats)

    out = {"sequential": seq_tps}
    print("engine,slots,clients,max_new,tokens_per_s,speedup_vs_sequential")
    print(f"sequential,1,{n_clients},{max_new},{seq_tps:.1f},1.00")
    for slots in SLOT_COUNTS:
        sys_b = ServingSystem(model, params, ccfg)
        sys_b.generate(prompts[:slots], max_new, num_slots=slots)  # warm
        tps = _tokens_per_s(
            lambda: sys_b.generate(prompts, max_new, mode="collm",
                                   num_slots=slots), total, repeats)
        out[slots] = tps
        if rows is not None:
            sched = max(sys_b._schedulers.values(),
                        key=lambda s: s.kv_cache_bytes())
            bpt = _kv_bytes_per_token(sched, mean_ctx)
            rows.append({"layout": "dense", "kv_dtype": "float32",
                         "slots": slots, "clients": n_clients,
                         "max_new": max_new, "tokens_per_s": tps,
                         "kv_bytes": sched.kv_cache_bytes(),
                         "kv_bytes_per_token": bpt,
                         "hbm_roofline_frac":
                             hbm_roofline_fraction(bpt, tps)})
        print(f"batched,{slots},{n_clients},{max_new},{tps:.1f},"
              f"{tps / seq_tps:.2f}")

    if check:
        speedup = out[8] / seq_tps
        assert speedup >= 3.0, (
            f"continuous batching at 8 slots is only {speedup:.2f}x the "
            f"sequential loop (acceptance bar: 3x)")
        print(f"# check passed: {speedup:.2f}x >= 3x at 8 slots")
    return out


PAGED_SLOT_COUNTS = (8, 16)
# |exit_rate(int8) - exit_rate(float32)| accuracy gate for the paged sweep:
# int8 KV perturbs logits near θ, so a few borderline tokens may flip which
# tier emits them — the gate bounds that drift (docs/kv_paging.md
# §Quantized pages), it does not demand bit-identical streams.
INT8_EXIT_DRIFT = 0.15
# int8 pages must cut the decode KV sweep by at least this factor; the
# analytic ratio for this model is ~3.4x (int8 data + fp32 per-row scales
# vs fp32 data), so 1.8x has headroom without being vacuous
INT8_BYTES_RATIO = 1.8


def _kv_bytes_per_token(sched, mean_ctx: float) -> int:
    """Achieved decode-step KV HBM bytes/token for one scheduler: paged
    layouts read the mapped pages of the mean-context slot (+ write one
    row); dense rings sweep the full per-slot ring regardless of context
    (the masked attention reads every slot)."""
    trees = [c for n in ("main_caches", "edge_caches", "cloud_caches")
             if (c := getattr(sched, n, None)) is not None]
    if sched.layout == "paged":
        return sum(decode_kv_bytes_per_token(t, int(mean_ctx),
                                             sched.pool.page_size)
                   for t in trees)
    total = sum(l.size * l.dtype.itemsize
                for t in trees for l in jax.tree.leaves(t))
    return total // sched.B


def _exit_rate(r: dict, total: int) -> float:
    st = r["stats"]
    return (st.exits_l1 + st.exits_l2) / total


def run_paged(csv: bool = False, *, n_clients: int = 16, max_new: int = 24,
              theta: float = 0.8, repeats: int = 1,
              kv_dtype: str = "float32", check: bool = False,
              rows: list = None) -> dict:
    """Dense vs. block-paged KV at 8/16 slots: aggregate decode tokens/s,
    pooled-KV device bytes, achieved decode KV HBM bytes/token, and the
    achieved-vs-roofline HBM fraction per (layout, kv_dtype).

    ``--kv-dtype int8`` (or ``both``) adds the int8 paged pool next to the
    float32 one.  With ``--check``:

      * paged float32 streams must be greedy token-identical to dense;
      * int8 paged KV must cut decode HBM bytes/token by >=
        ``INT8_BYTES_RATIO`` vs float32 paged at 8 slots;
      * the int8 exit-rate drift vs float32 stays within
        ``INT8_EXIT_DRIFT`` (bounded accuracy gate, not bit-identity)."""
    tiny = tiny_trained_model()
    model, params, data = tiny["model"], tiny["params"], tiny["data"]
    prompts = _requests(data, n_clients)
    total = n_clients * max_new
    mean_ctx = float(np.mean([len(p) for p in prompts])) + max_new / 2.0
    variants = [("dense", "float32"), ("paged", "float32")]
    if kv_dtype in ("int8", "both"):
        variants.append(("paged", "int8"))
    out: dict = {}
    print("layout,kv_dtype,slots,clients,max_new,tokens_per_s,kv_bytes,"
          "kv_bytes_per_token,hbm_roofline_frac,exit_rate")
    for layout, dtype in variants:
        ccfg = CollmConfig(theta=theta, kv_layout=layout,
                           kv_dtype=dtype if layout == "paged" else "float32")
        for slots in PAGED_SLOT_COUNTS:
            sys_b = ServingSystem(model, params, ccfg)
            sys_b.generate(prompts[:slots], max_new, num_slots=slots)  # warm
            res = {}
            def go():
                res["r"] = sys_b.generate(prompts, max_new, mode="collm",
                                          num_slots=slots)
            tps = _tokens_per_s(go, total, repeats)
            r = res["r"]
            sched = max(sys_b._schedulers.values(),
                        key=lambda s: s.kv_cache_bytes())
            kv_bytes = sched.kv_cache_bytes()
            bpt = _kv_bytes_per_token(sched, mean_ctx)
            frac = hbm_roofline_fraction(bpt, tps)
            row = {"layout": layout, "kv_dtype": dtype, "slots": slots,
                   "clients": n_clients, "max_new": max_new,
                   "tokens_per_s": tps, "kv_bytes": kv_bytes,
                   "kv_bytes_per_token": bpt, "hbm_roofline_frac": frac,
                   "exit_rate": _exit_rate(r, total)}
            out[(layout, dtype, slots)] = dict(row, tokens=r["tokens"])
            if rows is not None:
                rows.append(row)
            print(f"{layout},{dtype},{slots},{n_clients},{max_new},"
                  f"{tps:.1f},{kv_bytes},{bpt},{frac:.3e},"
                  f"{row['exit_rate']:.3f}")

    if check:
        for slots in PAGED_SLOT_COUNTS:
            d, p = out[("dense", "float32", slots)], \
                out[("paged", "float32", slots)]
            assert p["tokens"] == d["tokens"], (
                f"paged float32 streams must be greedy token-identical to "
                f"dense at {slots} slots")
        if ("paged", "int8", 8) in out:
            f32, i8 = out[("paged", "float32", 8)], out[("paged", "int8", 8)]
            ratio = f32["kv_bytes_per_token"] / i8["kv_bytes_per_token"]
            assert ratio >= INT8_BYTES_RATIO, (
                f"int8 paged KV cuts decode HBM bytes/token only "
                f"{ratio:.2f}x vs float32 at 8 slots "
                f"(gate: {INT8_BYTES_RATIO}x)")
            for slots in PAGED_SLOT_COUNTS:
                drift = abs(out[("paged", "int8", slots)]["exit_rate"]
                            - out[("paged", "float32", slots)]["exit_rate"])
                assert drift <= INT8_EXIT_DRIFT, (
                    f"int8 exit-rate drift {drift:.3f} at {slots} slots "
                    f"exceeds the {INT8_EXIT_DRIFT} accuracy gate")
            print(f"# check passed: paged f32 token-identical to dense; "
                  f"int8 bytes/token ratio {ratio:.2f}x >= "
                  f"{INT8_BYTES_RATIO}x; exit-rate drift within "
                  f"{INT8_EXIT_DRIFT}")
        else:
            print("# check passed: paged f32 token-identical to dense")
    return out


ASYNC_SLOTS = 8
# virtual edge compute per decode tick: A100-class edge partition on the
# tiny split (the absolute value only scales the virtual axis; the
# overlap-vs-blocking *ratio* is what the bench measures)
TICK_TIME_S = 0.01


def run_channel(csv: bool = False, *, n_clients: int = 16, max_new: int = 24,
                theta: float = 0.8, check: bool = False) -> dict:
    """Async cloud channel vs. blocking dispatch under identical WiFi-class
    ``NetworkParams``, at 8 slots, in virtual time; plus a deadline-miss
    trace (reply latency >> deadline) showing the latency-aware early exit
    committing edge tokens instead of stalling."""
    tiny = tiny_trained_model()
    model, params, data = tiny["model"], tiny["params"], tiny["data"]
    prompts = _requests(data, n_clients)
    total = n_clients * max_new
    ccfg = CollmConfig(theta=theta)
    out: dict = {}

    print("channel,dispatch,slots,virtual_s,virtual_ms_per_tok,wall_s,"
          "cloud_requests,deadline_misses,stall_s,overlap_s")
    for overlap in (False, True):
        ch = AsyncSimChannel(PAPER_NET, service_s=0.004)
        sysb = ServingSystem(model, params, ccfg)
        sysb.generate(prompts[:ASYNC_SLOTS], max_new,
                      num_slots=ASYNC_SLOTS, channel=ch,
                      tick_time_s=TICK_TIME_S, overlap=overlap)  # warm
        t0 = time.perf_counter()
        r = sysb.generate(prompts, max_new, mode="collm",
                          num_slots=ASYNC_SLOTS, channel=ch,
                          tick_time_s=TICK_TIME_S, overlap=overlap)
        wall = time.perf_counter() - t0
        st = r["stats"]
        name = "overlapped" if overlap else "blocking"
        out[name] = {"virtual_s": r["virtual_time"], "wall_s": wall,
                     "stats": st}
        print(f"wifi-sim,{name},{ASYNC_SLOTS},{r['virtual_time']:.3f},"
              f"{1e3 * r['virtual_time'] / total:.2f},{wall:.2f},"
              f"{st.cloud_requests},{st.deadline_misses},"
              f"{st.stall_s:.2f},{st.overlap_s:.2f}")

    # deadline-miss trace: every reply arrives long after its deadline
    ch = ScriptedChannel([0.5], deadline_s=0.02)
    sysd = ServingSystem(model, params, ccfg)
    r = sysd.generate(prompts, max_new, mode="collm", num_slots=ASYNC_SLOTS,
                      channel=ch, tick_time_s=TICK_TIME_S, fallback_after=4)
    st = r["stats"]
    complete = all(len(t) == max_new for t in r["tokens"])
    out["deadline"] = {"virtual_s": r["virtual_time"], "stats": st,
                       "complete": complete}
    print(f"deadline-trace,overlapped,{ASYNC_SLOTS},{r['virtual_time']:.3f},"
          f"{1e3 * r['virtual_time'] / total:.2f},-,{st.cloud_requests},"
          f"{st.deadline_misses},{st.stall_s:.2f},{st.overlap_s:.2f}")
    print(f"# deadline trace: {st.deadline_misses} misses -> edge-committed "
          f"tokens, {st.fallbacks} standalone fallbacks, all streams "
          f"complete: {complete}")

    if check:
        v_block = out["blocking"]["virtual_s"]
        v_over = out["overlapped"]["virtual_s"]
        assert v_over < v_block, (
            f"overlapped dispatch ({v_over:.3f}s virtual) should beat the "
            f"blocking path ({v_block:.3f}s virtual) at {ASYNC_SLOTS} slots")
        assert complete and st.deadline_misses > 0, (
            "deadline-miss trace must complete every stream via "
            "edge-committed tokens")
        print(f"# check passed: overlapped {v_over:.3f}s < blocking "
              f"{v_block:.3f}s virtual; deadline trace completed with "
              f"{st.deadline_misses} misses")
    return out


OVERSUB_SLOTS = 4
OVERSUB_FRAC = 0.6       # page budget as a fraction of worst-case demand


def run_oversubscribe(csv: bool = False, *, n_clients: int = 8,
                      max_new: int = 24, theta: float = 0.8,
                      check: bool = False) -> dict:
    """Optimistic admission + preemption vs. worst-case (admission-blocked)
    paging at a page budget of ~60% of the concurrent worst-case demand
    (docs/kv_paging.md §Preemption).

    ``blocked`` keeps ``preemption="off"``: admission reserves the worst
    case, so the shrunken pool caps concurrency below the slot count and
    the queue drains in waves.  ``recompute``/``swap`` admit every slot on
    its prompt pages and preempt victims when the free list runs dry.  All
    three emit token-identical streams (asserted against an unconstrained
    paged run); the virtual makespan (``tick_time_s`` per decode tick,
    zero-latency cloud) isolates the concurrency win.  ``--check`` asserts
    >= 1 real preemption and a preemptive makespan below the blocked one."""
    tiny = tiny_trained_model()
    model, params, data = tiny["model"], tiny["params"], tiny["data"]
    prompts = _requests(data, n_clients)
    ccfg = lambda **kw: CollmConfig(theta=theta, kv_layout="paged", **kw)
    ps = ccfg().page_size
    worst = max((len(p) + max_new - 1) // ps + 1 for p in prompts)
    demand = OVERSUB_SLOTS * worst
    budget = max(worst, int(OVERSUB_FRAC * demand))

    ref_sys = ServingSystem(model, params, ccfg())
    ref = ref_sys.generate(prompts, max_new, mode="collm",
                           num_slots=OVERSUB_SLOTS)["tokens"]

    out: dict = {}
    print(f"# page budget {budget}/{demand} pages "
          f"({100 * budget / demand:.0f}% of worst-case demand)")
    print("paging,slots,pages,virtual_s,preemptions,swapped_kb,"
          "tokens_equal")
    for variant in ("blocked", "recompute", "swap"):
        pre = "off" if variant == "blocked" else variant
        sysv = ServingSystem(model, params, ccfg(preemption=pre))
        r = sysv.generate(prompts, max_new, mode="collm",
                          num_slots=OVERSUB_SLOTS, num_pages=budget,
                          tick_time_s=TICK_TIME_S)
        sched = next(iter(sysv._schedulers.values()))
        equal = r["tokens"] == ref
        # NB ``sched.swap`` has __len__ (empty after a clean drain): test
        # for None, not truthiness
        sw_kb = (sched.swap.stats.bytes_out / 1e3
                 if sched.swap is not None else 0.0)
        out[variant] = {"virtual_s": r["virtual_time"],
                        "preemptions": sched.preemptions,
                        "tokens_equal": equal}
        print(f"{variant},{OVERSUB_SLOTS},{budget},{r['virtual_time']:.3f},"
              f"{sched.preemptions},{sw_kb:.1f},{equal}")

    if check:
        assert all(v["tokens_equal"] for v in out.values()), \
            "oversubscribed streams must be token-identical to the " \
            "unconstrained paged run"
        assert out["blocked"]["preemptions"] == 0
        for variant in ("recompute", "swap"):
            assert out[variant]["preemptions"] >= 1, \
                f"{variant}: the {budget}-page budget should force at " \
                f"least one preemption"
            assert out[variant]["virtual_s"] < out["blocked"]["virtual_s"], (
                f"{variant} ({out[variant]['virtual_s']:.3f}s virtual) "
                f"should beat admission-blocked paging "
                f"({out['blocked']['virtual_s']:.3f}s virtual)")
        print(f"# check passed: recompute {out['recompute']['virtual_s']:.3f}s"
              f" / swap {out['swap']['virtual_s']:.3f}s < blocked "
              f"{out['blocked']['virtual_s']:.3f}s virtual; streams "
              f"identical")
    return out


PREFIX_SLOTS = 4
PREFIX_PAGE_SIZE = 8     # small pages -> several shared chunks per prompt


def _prefix_requests(data, n_clients: int, page_size: int):
    """N prompts sharing a common system prefix (2 full pages + a partial
    tail page), each with a distinct continuation, plus two exact
    duplicates of earlier prompts (whole-prompt terminal hits)."""
    system = np.asarray(data.sample_tokens(2 * page_size + 3))
    prompts = []
    for i in range(max(1, n_clients - 2)):
        suffix = np.asarray(data.sample_tokens(4 + i % 6))
        prompts.append(np.concatenate([system, suffix]).astype(np.int32))
    while len(prompts) < n_clients:
        prompts.append(prompts[len(prompts) % 2].copy())
    return prompts


def run_prefix_share(csv: bool = False, *, n_clients: int = 8,
                     max_new: int = 16, theta: float = 0.8,
                     check: bool = False, rows: list = None) -> dict:
    """Radix prefix sharing + copy-on-write vs. plain chunked prefill
    (docs/kv_paging.md §Prefix sharing): N streams whose prompts open with
    a common system prefix, admitted through the chunked-prefill path on
    the paged pool, with and without ``prefix_share``.  Sharing maps the
    prefix pages into every stream's block table (refcounted), skips their
    prefill chunks AND their hidden-state uploads, and copy-on-writes the
    partial tail page when each stream's first divergent token lands.
    Exact-duplicate prompts hit a cached terminal (zero prefill compute,
    memoized first token).  Both variants must emit token-identical
    streams.  ``--check`` asserts, for float32 and int8 paged pools:
    fewer prefill chunks, fewer page allocations, fewer uploaded bytes,
    >0 prefix-hit tokens, >=1 CoW copy, token-identical output — plus an
    all-terminal second wave (re-sent prompts, zero prefill chunks)."""
    tiny = tiny_trained_model()
    model, params, data = tiny["model"], tiny["params"], tiny["data"]
    ps = PREFIX_PAGE_SIZE
    prompts = _prefix_requests(data, n_clients, ps)
    max_len = max(len(p) for p in prompts)
    max_seq = -(-(max_len + max_new) // ps) * ps + ps
    gkw = dict(num_slots=PREFIX_SLOTS, max_seq=max_seq, max_ctx=max_seq,
               num_pages=PREFIX_SLOTS * (max_seq // ps) * 2)

    out: dict = {}
    print("kv_dtype,variant,prefill_chunks,prefix_hit_tokens,cow_copies,"
          "page_allocs,upload_kb,tokens_equal")
    for kv_dtype in ("float32", "int8"):
        ccfg = lambda **kw: CollmConfig(theta=theta, kv_layout="paged",
                                        page_size=ps, kv_dtype=kv_dtype,
                                        chunked_prefill=True, **kw)
        r_un = ServingSystem(model, params, ccfg()).generate(
            prompts, max_new, mode="collm", **gkw)
        sys_sh = ServingSystem(model, params, ccfg(prefix_share=True))
        r_sh = sys_sh.generate(prompts, max_new, mode="collm", **gkw)
        # second wave on the warm system: every re-sent prompt should hit
        # a cached terminal (zero prefill compute, memoized first token)
        r_w2 = sys_sh.generate(prompts[:2], max_new, mode="collm", **gkw)
        out[kv_dtype] = {}
        for variant, r in (("unshared", r_un), ("shared", r_sh)):
            st = r["stats"]
            equal = r["tokens"] == r_un["tokens"]
            row = {"mode": "prefix_share", "kv_dtype": kv_dtype,
                   "variant": variant, "clients": n_clients,
                   "slots": PREFIX_SLOTS, "max_new": max_new,
                   "prefill_chunks": st.prefill_chunks,
                   "prefix_hit_tokens": st.prefix_hit_tokens,
                   "cow_copies": st.cow_copies,
                   "page_allocs": r["pool_stats"]["allocs"],
                   "upload_bytes": st.upload_bytes,
                   "tokens_equal": equal}
            out[kv_dtype][variant] = row
            if rows is not None:
                rows.append(row)
            print(f"{kv_dtype},{variant},{st.prefill_chunks},"
                  f"{st.prefix_hit_tokens},{st.cow_copies},"
                  f"{row['page_allocs']},{st.upload_bytes / 1e3:.1f},"
                  f"{equal}")
        out[kv_dtype]["wave2"] = {
            "prefill_chunks": r_w2["stats"].prefill_chunks,
            "tokens_equal": r_w2["tokens"] == r_un["tokens"][:2]}

    if check:
        for kv_dtype, o in out.items():
            un, sh, w2 = o["unshared"], o["shared"], o["wave2"]
            assert sh["tokens_equal"], \
                f"{kv_dtype}: shared streams diverge from unshared"
            assert sh["prefill_chunks"] < un["prefill_chunks"], (
                f"{kv_dtype}: sharing should skip prefix prefill chunks "
                f"({sh['prefill_chunks']} vs {un['prefill_chunks']})")
            assert sh["prefix_hit_tokens"] > 0, \
                f"{kv_dtype}: no prefix hits recorded"
            assert sh["cow_copies"] >= 1, (
                f"{kv_dtype}: the partial tail page must be "
                f"copy-on-written at least once")
            assert sh["upload_bytes"] < un["upload_bytes"], (
                f"{kv_dtype}: deduped uploads should cut wire bytes "
                f"({sh['upload_bytes']} vs {un['upload_bytes']})")
            assert sh["page_allocs"] < un["page_allocs"], (
                f"{kv_dtype}: shared pages should cut fresh allocations "
                f"({sh['page_allocs']} vs {un['page_allocs']})")
            assert w2["tokens_equal"] and w2["prefill_chunks"] == 0, (
                f"{kv_dtype}: wave-2 identical prompts should be "
                f"all-terminal (got {w2['prefill_chunks']} chunks)")
        f32 = out["float32"]
        print(f"# check passed: {f32['shared']['prefill_chunks']} vs "
              f"{f32['unshared']['prefill_chunks']} prefill chunks, "
              f"{f32['shared']['page_allocs']} vs "
              f"{f32['unshared']['page_allocs']} page allocs, "
              f"{f32['shared']['upload_bytes']} vs "
              f"{f32['unshared']['upload_bytes']} upload bytes "
              f"(float32; int8 likewise); streams identical, wave 2 "
              f"all-terminal")
    return out


# high-RTT WAN-class link for the drafting sweep: the per-request RTT tax
# and the per-request cloud service cost are what k-token drafts amortize
# (k tokens per verification request instead of one request per token)
SPEC_NET = NetworkParams(up_bw=3.8e6, down_bw=8e6, rtt=0.08)
SPEC_SERVICE_S = 0.006


def run_spec(csv: bool = False, *, n_clients: int = 16, max_new: int = 24,
             theta: float = 0.8, spec_k: int = 4, check: bool = False,
             rows: list = None) -> dict:
    """Multi-token edge drafting vs. the classic 1-token speculative path
    (docs/async_transport.md §Speculative): both overlapped at 8 slots on
    the same high-RTT WAN-class channel with a per-request cloud service
    point.  spec_k=k ships up to k provisional tokens per verification
    request, so a below-θ burst costs ~1/k as many requests — each of
    which pays RTT and serializes through the service point.  Streams stay
    greedy token-identical to the blocking non-speculative run (infinite
    deadline).  With ``--check`` asserts spec_k=k beats spec_k=1 on
    virtual makespan at 8 slots and that the acceptance rate is reported."""
    tiny = tiny_trained_model()
    model, params, data = tiny["model"], tiny["params"], tiny["data"]
    prompts = _requests(data, n_clients)
    total = n_clients * max_new

    # blocking non-speculative reference: drafting must be invisible in
    # output space, whatever k
    ref = ServingSystem(model, params, CollmConfig(theta=theta)).generate(
        prompts, max_new, mode="collm", num_slots=ASYNC_SLOTS)["tokens"]

    ks = sorted({1, spec_k})
    out: dict = {}
    print("spec_k,slots,virtual_s,virtual_ms_per_tok,requests,draft_tokens,"
          "accepted,accept_rate,mean_accept_len,rewinds,tokens_equal")
    for k in ks:
        ccfg = CollmConfig(theta=theta, speculative=True, spec_k=k)
        sysk = ServingSystem(model, params, ccfg)
        sysk.generate(prompts[:ASYNC_SLOTS], max_new, num_slots=ASYNC_SLOTS,
                      channel=AsyncSimChannel(SPEC_NET,
                                              service_s=SPEC_SERVICE_S),
                      tick_time_s=TICK_TIME_S)               # warm compile
        ch = AsyncSimChannel(SPEC_NET, service_s=SPEC_SERVICE_S)
        r = sysk.generate(prompts, max_new, mode="collm",
                          num_slots=ASYNC_SLOTS, channel=ch,
                          tick_time_s=TICK_TIME_S)
        st = r["stats"]
        accept_rate = (st.accepted_tokens / st.draft_tokens
                       if st.draft_tokens else 0.0)
        mean_len = (float(np.mean(st.accept_lens))
                    if st.accept_lens else 0.0)
        equal = r["tokens"] == ref
        row = {"spec_k": k, "slots": ASYNC_SLOTS, "clients": n_clients,
               "max_new": max_new, "virtual_s": r["virtual_time"],
               "requests": r["channel_stats"]["requests"],
               "draft_tokens": st.draft_tokens,
               "accepted_tokens": st.accepted_tokens,
               "accept_rate": accept_rate, "mean_accept_len": mean_len,
               "spec_rewinds": st.spec_rewinds, "tokens_equal": equal}
        out[k] = row
        if rows is not None:
            rows.append(row)
        print(f"{k},{ASYNC_SLOTS},{r['virtual_time']:.3f},"
              f"{1e3 * r['virtual_time'] / total:.2f},{row['requests']},"
              f"{st.draft_tokens},{st.accepted_tokens},{accept_rate:.2%},"
              f"{mean_len:.2f},{st.spec_rewinds},{equal}")

    if check:
        assert spec_k > 1, "--check needs --spec-k > 1 (nothing to compare)"
        v1, vk = out[1]["virtual_s"], out[spec_k]["virtual_s"]
        assert vk < v1, (
            f"spec_k={spec_k} drafting ({vk:.3f}s virtual) should beat the "
            f"1-token speculative path ({v1:.3f}s virtual) at "
            f"{ASYNC_SLOTS} slots on the high-RTT link")
        assert out[spec_k]["requests"] < out[1]["requests"], (
            "k-token drafts must coalesce verification requests")
        assert out[spec_k]["draft_tokens"] > 0 \
            and out[spec_k]["accept_rate"] > 0.0, \
            "acceptance rate must be measured and reported"
        assert all(v["tokens_equal"] for v in out.values()), \
            "draft streams must stay token-identical to the blocking run"
        print(f"# check passed: spec_k={spec_k} {vk:.3f}s < spec_k=1 "
              f"{v1:.3f}s virtual at {ASYNC_SLOTS} slots "
              f"({out[spec_k]['requests']} vs {out[1]['requests']} requests, "
              f"accept rate {out[spec_k]['accept_rate']:.2%}); streams "
              f"identical to blocking")
    return out


# virtual cost of ONE batched cloud service step (A100-class cloud
# partition); the batching window the cloud waits to accumulate arrivals
CLOUD_SERVICE_S = 0.008
CLOUD_WINDOW_S = 0.004


def run_cloud_batch(csv: bool = False, *, n_clients: int = 4,
                    max_new: int = 24, theta: float = 0.8,
                    check: bool = False) -> dict:
    """Multi-client sweep (paper §5, Fig 4): N edge engines, each its own
    WiFi link and virtual clock, sharing ONE cloud.  ``fifo`` prices the
    cloud as a per-request queue (every request occupies the server for
    ``CLOUD_SERVICE_S`` back-to-back) with per-engine cloud compute;
    ``batched`` routes compute through the shared ``CloudBatcher`` (one
    masked cloud step per wave of concurrent requests) and prices it with
    a batching service point.  ``--check`` asserts the batched cloud
    virtual makespan beats per-request FIFO at N>=4 and that both emit
    token-identical streams to N independent sync runs."""
    tiny = tiny_trained_model()
    model, params, data = tiny["model"], tiny["params"], tiny["data"]
    prompts = _requests(data, n_clients)
    ccfg = CollmConfig(theta=theta)

    # reference: each client run independently on a blocking SyncChannel
    ref_sys = ServingSystem(model, params, ccfg)
    ref = [ref_sys.generate([p], max_new, mode="collm", num_slots=1)
           ["tokens"][0] for p in prompts]

    n_layers = model.cfg.n_layers
    cloud_frac = (n_layers - model.cfg.exit_layers[0]) / n_layers
    out: dict = {}
    print("cloud,clients,virtual_s,cloud_busy_s,steps,mean_batch,"
          "requests,offload_pct,tokens_equal")
    for variant in ("fifo", "batched"):
        # one client has nobody to coalesce with: both variants are FIFO
        batched = variant == "batched" and n_clients > 1
        svc = CloudServicePoint(
            CLOUD_SERVICE_S,
            batch_window_s=CLOUD_WINDOW_S if batched else 0.0,
            max_batch=n_clients if batched else 1)
        chans = [AsyncSimChannel(PAPER_NET, service=svc)
                 for _ in range(n_clients)]
        sysm = ServingSystem(model, params, ccfg)
        r = sysm.generate_multi(prompts, max_new, cloud_batch=batched,
                                channels=chans, tick_time_s=TICK_TIME_S)
        st = r["stats"]
        # cloud work the edge kept OFF the cloud, vs. the cloud-only
        # deployment (every token, all layers) — the paper's headline
        offload = 100.0 * (1.0 - st.request_rate * cloud_frac)
        b = r.get("batcher", {})
        equal = r["tokens"] == ref
        out[variant] = {"virtual_s": r["virtual_time"],
                        "cloud_busy_s": svc.busy_s,
                        "steps": b.get("steps", svc.batches),
                        "mean_batch": b.get("mean_batch", 1.0),
                        "offload_pct": offload, "tokens_equal": equal}
        print(f"{variant},{n_clients},{r['virtual_time']:.3f},"
              f"{svc.busy_s:.3f},{out[variant]['steps']},"
              f"{out[variant]['mean_batch']},{st.cloud_requests},"
              f"{offload:.1f},{equal}")

    if check:
        v_f, v_b = out["fifo"]["virtual_s"], out["batched"]["virtual_s"]
        assert n_clients >= 4, "--check needs --clients >= 4"
        assert v_b < v_f, (
            f"batched cloud ({v_b:.3f}s virtual) should beat per-request "
            f"FIFO ({v_f:.3f}s virtual) at {n_clients} clients")
        assert out["batched"]["tokens_equal"] and out["fifo"]["tokens_equal"], \
            "multi-client streams must be token-identical to independent " \
            "sync runs"
        print(f"# check passed: batched {v_b:.3f}s < fifo {v_f:.3f}s "
              f"virtual at {n_clients} clients; streams identical to "
              f"independent runs")
    return out


def run_cloud_tp(csv: bool = False, *, n_clients: int = 3, max_new: int = 8,
                 theta: float = 0.8, dp: int = 2, tp: int = 4,
                 check: bool = False, rows: list = None) -> dict:
    """Cloud tensor parallelism (docs/sharding.md): the tiny EE model
    served with the cloud partition's steps compiled against a (dp x tp)
    host-device mesh vs. the single-device path.  Reports token identity,
    per-device cloud param bytes (the analytic
    ``estimate_param_bytes_per_device`` AND what ``device_put`` actually
    committed to device 0), trace counts across two ``generate_multi``
    fleets (the per-CoLLM memoization must keep N engines on one trace
    per step), and the collective traffic parsed out of the sharded
    ``cloud_step_masked`` HLO — predicted all-reduce / all-gather wire
    bytes per device per cloud step, the sharded counterpart of the KV
    bytes/token roofline rows.  With ``--check`` asserts token identity,
    estimate == placed bytes with the expected model-axis shrink (GQA KV
    projections and norms replicate, so the bar is >= 0.6*tp), zero
    re-traces on the second fleet, and >= 1 all-reduce in the step."""
    import jax.numpy as jnp

    from repro.core.transport import quantize
    from repro.launch import sharding as shardlib
    from repro.roofline.collectives import (parse_collectives,
                                            total_wire_bytes)
    from repro.serving.mesh_exec import mesh_context

    need = dp * tp
    if len(jax.devices()) < need:
        raise SystemExit(
            f"--cloud-tp {tp} --cloud-dp {dp} needs {need} devices but "
            f"only {len(jax.devices())} are visible; export "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={need}")

    tiny = tiny_trained_model()
    model, params, data = tiny["model"], tiny["params"], tiny["data"]
    prompts = _requests(data, n_clients)

    ref = ServingSystem(model, params, CollmConfig(theta=theta)
                        ).generate_multi(prompts, max_new)
    sys_tp = ServingSystem(model, params,
                           CollmConfig(theta=theta, cloud_mesh=(dp, tp)))
    r1 = sys_tp.generate_multi(prompts, max_new)
    mc = mesh_context(sys_tp.collm)
    first_fleet = dict(mc.trace_counts)
    r2 = sys_tp.generate_multi(prompts, max_new)
    retraces = sum(mc.trace_counts.values()) - sum(first_fleet.values())
    identical = (r1["tokens"] == ref["tokens"]
                 and r2["tokens"] == ref["tokens"])

    # per-device param bytes: analytic estimate vs device_put's shards
    est = shardlib.estimate_param_bytes_per_device(
        model.param_specs(), mc.mesh, fsdp=False,
        head_dim=model.cfg.resolved_head_dim)
    dev0 = mc.mesh.devices.flat[0]
    placed = sum(s.data.nbytes for l in jax.tree.leaves(sys_tp.params)
                 for s in l.addressable_shards if s.device == dev0)
    total = sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(params))
    shrink = total / placed

    # collective traffic of one sharded masked cloud step at B rows
    B, d = n_clients, model.cfg.d_model
    caches = mc.shard_caches(sys_tp.collm.init_cloud_cache(B, 64), batch=B)
    upload = quantize(jnp.zeros((B, 1, d), jnp.float32),
                      sys_tp.collm.ccfg.wire_format)
    pos = jnp.zeros((B,), jnp.int32)
    mask = jnp.ones((B,), bool)
    with shardlib.use_policy(mc.policy):
        hlo = jax.jit(sys_tp.collm.cloud_step_masked).lower(
            sys_tp.params, upload, caches, pos, mask).compile().as_text()
    coll = parse_collectives(hlo, need)
    ar = coll.get("all-reduce", {"count": 0, "wire_bytes": 0.0})
    ag = coll.get("all-gather", {"count": 0, "wire_bytes": 0.0})

    row = {"mode": "cloud_tp", "mesh": f"{dp}x{tp}", "devices": need,
           "clients": n_clients, "max_new": max_new,
           "tokens_equal": identical,
           "param_bytes_total": total, "param_bytes_per_dev": placed,
           "param_bytes_per_dev_est": est, "shrink_x": shrink,
           "trace_counts": first_fleet, "retraces_2nd_fleet": retraces,
           "allreduce_count": ar["count"],
           "allreduce_wire_bytes": ar["wire_bytes"],
           "allgather_count": ag["count"],
           "allgather_wire_bytes": ag["wire_bytes"],
           "coll_wire_bytes_per_step": total_wire_bytes(coll)}
    if rows is not None:
        rows.append(row)
    print("mesh,devices,tokens_equal,param_KB_per_dev,param_KB_est,"
          "shrink_x,retraces_2nd_fleet,allreduce_n,allreduce_KB,"
          "allgather_n,allgather_KB,coll_KB_per_step")
    print(f"{dp}x{tp},{need},{identical},{placed / 1e3:.1f},"
          f"{est / 1e3:.1f},{shrink:.2f},{retraces},{ar['count']},"
          f"{ar['wire_bytes'] / 1e3:.2f},{ag['count']},"
          f"{ag['wire_bytes'] / 1e3:.2f},"
          f"{total_wire_bytes(coll) / 1e3:.2f}")

    if check:
        assert identical, "sharded cloud steps must be token-identical " \
            "to the single-device path"
        assert abs(placed - est) <= 1e-6 * max(est, 1), (
            f"placed per-device bytes {placed} != estimate {est}")
        assert shrink >= 0.6 * tp, (
            f"per-device param bytes shrank only {shrink:.2f}x on a "
            f"model={tp} mesh (expected ~{tp}x less replicated leaves)")
        assert retraces == 0, (
            f"second generate_multi fleet re-traced {retraces} steps; "
            f"the per-CoLLM jit memoization must hold across engines")
        assert ar["count"] >= 1, (
            "a row-parallel cloud step must all-reduce partial sums; "
            "none found in the compiled HLO")
        print(f"# check passed: {dp}x{tp} mesh token-identical, "
              f"{shrink:.2f}x per-device param shrink (est==placed), "
              f"0 re-traces on 2nd fleet, {ar['count']} all-reduces "
              f"({total_wire_bytes(coll) / 1e3:.1f}KB wire/step)")
    return row


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--clients", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--theta", type=float, default=0.8)
    ap.add_argument("--repeats", type=int, default=1)
    ap.add_argument("--check", action="store_true",
                    help="assert >=3x speedup at 8 slots (sync) / overlap "
                         "beats blocking + deadline trace completes (sim)")
    ap.add_argument("--kv-layout", choices=("dense", "paged", "both"),
                    default="dense",
                    help="paged/both: compare KV layouts at 8/16 slots")
    ap.add_argument("--kv-dtype", choices=("float32", "int8", "both"),
                    default="float32",
                    help="int8/both: add the int8 paged pool to the layout "
                         "sweep (bytes/token + accuracy gates with --check)")
    ap.add_argument("--json", default="BENCH_throughput.json",
                    help="machine-readable output of the slot/layout/dtype "
                         "sweeps (written by the sync + paged paths)")
    ap.add_argument("--channel", choices=("sync", "sim"), default="sync",
                    help="sim: async-transport comparison (overlap vs "
                         "blocking + deadline-miss trace) instead of the "
                         "slot sweep")
    ap.add_argument("--cloud-batch", action="store_true",
                    help="multi-client sweep: N edge engines sharing one "
                         "cloud, batched CloudBatcher vs per-request FIFO")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="drafting sweep: spec_k=1 vs spec_k=K overlapped "
                         "at 8 slots on a high-RTT link (--check asserts "
                         "K-token drafts cut the virtual makespan)")
    ap.add_argument("--oversubscribe", action="store_true",
                    help="paged-KV preemption sweep: page budget at ~60%% "
                         "of worst-case demand, optimistic+preemptive vs "
                         "admission-blocked paging")
    ap.add_argument("--prefix-share", action="store_true",
                    help="radix prefix sharing sweep: N streams with a "
                         "common system prompt, shared vs. unshared "
                         "chunked prefill on float32 + int8 paged pools "
                         "(--check asserts fewer chunks/pages/upload "
                         "bytes, token-identical streams)")
    ap.add_argument("--cloud-tp", type=int, default=0,
                    help="cloud tensor-parallel sweep: serve with the "
                         "cloud partition compiled against a "
                         "(--cloud-dp x N) mesh vs. single device "
                         "(needs XLA_FLAGS="
                         "--xla_force_host_platform_device_count)")
    ap.add_argument("--cloud-dp", type=int, default=2,
                    help="data-axis size of the --cloud-tp mesh")
    args = ap.parse_args()
    if args.cloud_tp:
        rows = []
        run_cloud_tp(n_clients=args.clients, max_new=args.max_new,
                     theta=args.theta, dp=args.cloud_dp, tp=args.cloud_tp,
                     check=args.check, rows=rows)
        with open(args.json, "w") as f:
            json.dump(rows, f, indent=1)
        print(f"# wrote {len(rows)} rows to {args.json}")
        return
    if args.prefix_share:
        rows = []
        run_prefix_share(n_clients=args.clients, max_new=args.max_new,
                         theta=args.theta, check=args.check, rows=rows)
        with open(args.json, "w") as f:
            json.dump(rows, f, indent=1)
        print(f"# wrote {len(rows)} rows to {args.json}")
        return
    if args.spec_k:
        rows = []
        run_spec(n_clients=args.clients, max_new=args.max_new,
                 theta=args.theta, spec_k=args.spec_k, check=args.check,
                 rows=rows)
        with open(args.json, "w") as f:
            json.dump(rows, f, indent=1)
        print(f"# wrote {len(rows)} rows to {args.json}")
        return
    if args.oversubscribe:
        run_oversubscribe(n_clients=args.clients, max_new=args.max_new,
                          theta=args.theta, check=args.check)
        return
    if args.cloud_batch:
        run_cloud_batch(n_clients=args.clients, max_new=args.max_new,
                        theta=args.theta, check=args.check)
        return
    if args.channel == "sim":
        run_channel(n_clients=args.clients, max_new=args.max_new,
                    theta=args.theta, check=args.check)
        return
    rows: list = []
    if args.kv_layout in ("dense", "both"):
        run(n_clients=args.clients, max_new=args.max_new, theta=args.theta,
            repeats=args.repeats, check=args.check, rows=rows)
    if args.kv_layout in ("paged", "both"):
        run_paged(n_clients=args.clients, max_new=args.max_new,
                  theta=args.theta, repeats=args.repeats,
                  kv_dtype=args.kv_dtype, check=args.check, rows=rows)
    if rows:
        with open(args.json, "w") as f:
            json.dump(rows, f, indent=1)
        print(f"# wrote {len(rows)} rows to {args.json}")


if __name__ == "__main__":
    main()
