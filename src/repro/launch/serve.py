"""Serving launcher: CE-CoLLM co-inference over synthetic prompts.

    PYTHONPATH=src python -m repro.launch.serve --arch granite-moe-3b-a800m \
        --smoke --mode collm --theta 0.8 --clients 2 --max-new 16

``--channel sim`` prices every cloud request with WiFi-class network
parameters in virtual time (the engine overlaps edge decode with in-flight
replies); ``--deadline`` arms the latency-aware early exit.

``--cloud-batch`` switches to the multi-client topology (paper §5): each
client is its own single-slot engine and the shared ``CloudBatcher``
coalesces their concurrent cloud requests into one masked cloud step over
a pooled cloud cache; with ``--channel sim`` the engines' channels share
one batching ``CloudServicePoint`` (``--batch-window``).
"""
from __future__ import annotations

import argparse
import math

import jax
import jax.numpy as jnp

from repro.configs.registry import get_config, get_smoke_config
from repro.core.collm import CoLLM, CollmConfig
from repro.core.netsim import NetworkParams
from repro.core.transport import AsyncSimChannel, CloudServicePoint
from repro.core.workload import ArrivalProcess, arrival_times
from repro.data.pipeline import DataConfig, SyntheticCorpus
from repro.launch.compile_cache import enable_compile_cache
from repro.models.registry import build_model
from repro.models.transformer import Model
from repro.serving.adaptive import AdaptiveConfig, ResumeCostModel
from repro.serving.engine import ServingSystem, token_agreement
from repro.serving.mesh_exec import mesh_context
from repro.training.checkpoint import load_checkpoint


def served_model(arch: str, smoke: bool) -> Model:
    """The model as the launcher serves it.  A published config is served
    in bfloat16 (ee-llm-7b: 13.5 GB of params against 16 GB of v5e HBM;
    float32 would not fit); smoke configs stay float32."""
    cfg = get_smoke_config(arch) if smoke else get_config(arch)
    return build_model(cfg, param_dtype=jnp.float32 if smoke
                       else jnp.bfloat16)


def init_params(model: Model, ccfg: CollmConfig = CollmConfig(),
                seed: int = 0):
    """Seeded params from one jitted init, so only the stacked arrays ever
    reach the device.  With ``ccfg.cloud_mesh`` set they are made straight
    onto the cloud mesh — never whole on one device first."""
    sh = mesh_context(CoLLM(model, ccfg)).params_shardings(
        model.param_specs())
    return jax.jit(model.init, out_shardings=sh)(jax.random.PRNGKey(seed))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="ee-llm-7b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--mode", default="collm",
                    choices=["collm", "standalone", "cloud"])
    ap.add_argument("--theta", type=float, default=0.8)
    ap.add_argument("--wire", default="float16",
                    choices=["float32", "float16", "int8"])
    ap.add_argument("--backfill", action="store_true")
    ap.add_argument("--clients", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--kv-layout", default="dense",
                    choices=["dense", "paged"],
                    help="paged: block-paged KV pool shared across slots")
    ap.add_argument("--kv-dtype", default="float32",
                    choices=["float32", "int8"],
                    help="int8: quantized KV pages with per-row absmax "
                         "scales (needs --kv-layout paged)")
    ap.add_argument("--num-pages", type=int, default=None,
                    help="paged pool size; below the worst-case demand it "
                         "oversubscribes (pair with --preemption)")
    ap.add_argument("--preemption", default="off",
                    choices=["off", "recompute", "swap"],
                    help="optimistic paged admission: preempt victim "
                         "streams on OutOfPages and resume by re-prefill "
                         "(recompute) or host page swap (swap)")
    ap.add_argument("--preempt-policy", default="youngest",
                    choices=["youngest", "fewest-pages", "lru"],
                    help="victim selection under --preemption")
    ap.add_argument("--chunked-prefill", action="store_true",
                    help="prefill prompts one page-sized chunk per tick "
                         "interleaved with decode (needs --kv-layout "
                         "paged)")
    ap.add_argument("--prefix-share", action="store_true",
                    help="radix prefix cache: streams sharing a prompt "
                         "prefix map the same refcounted KV pages, "
                         "copy-on-write on divergence (needs "
                         "--chunked-prefill)")
    ap.add_argument("--channel", default="sync", choices=["sync", "sim"],
                    help="sim: WiFi-class async channel in virtual time")
    ap.add_argument("--deadline", type=float, default=math.inf,
                    help="per-request reply budget (virtual s); a miss "
                         "commits the edge token")
    ap.add_argument("--tick-time", type=float, default=0.01,
                    help="virtual edge compute per decode tick (sim)")
    ap.add_argument("--speculative", action="store_true",
                    help="commit provisional edge tokens while cloud "
                         "replies are in flight")
    ap.add_argument("--spec-k", type=int, default=1,
                    help="edge draft length: ship up to k provisional "
                         "tokens per verification request (needs "
                         "--speculative; 1 = classic speculative path)")
    ap.add_argument("--cloud-tp", type=int, default=0,
                    help="model-axis size of the cloud tensor-parallel "
                         "mesh; the cloud partition's steps compile "
                         "against a (--cloud-dp x N) device grid "
                         "(docs/sharding.md; 0 = single device)")
    ap.add_argument("--cloud-dp", type=int, default=1,
                    help="data-axis (batch) size of the cloud mesh "
                         "(needs --cloud-tp)")
    ap.add_argument("--cloud-batch", action="store_true",
                    help="multi-client mode: one engine per client, cloud "
                         "requests coalesced by the shared CloudBatcher")
    ap.add_argument("--batch-window", type=float, default=0.004,
                    help="cloud service accumulation window (virtual s, "
                         "--cloud-batch with --channel sim)")
    ap.add_argument("--service-s", type=float, default=0.008,
                    help="virtual cost of one cloud service step "
                         "(--channel sim)")
    ap.add_argument("--arrival-rate", type=float, default=0.0,
                    help="open-loop fleet replay (docs/fleet_sim.md): mean "
                         "request arrivals per virtual second (0 = closed "
                         "loop, the whole backlog at t=0)")
    ap.add_argument("--arrival-cv2", type=float, default=1.0,
                    help="interarrival squared coefficient of variation "
                         "(1 = Poisson, >1 = bursty gamma renewals)")
    ap.add_argument("--diurnal-amp", type=float, default=0.0,
                    help="sinusoidal arrival-rate modulation depth in "
                         "[0, 1)")
    ap.add_argument("--diurnal-period", type=float, default=10.0,
                    help="diurnal modulation period (virtual s)")
    ap.add_argument("--arrival-seed", type=int, default=0)
    ap.add_argument("--slo-ttft", type=float, default=None,
                    help="per-request time-to-first-token target "
                         "(virtual s) folded into SLO attainment")
    ap.add_argument("--slo-tpot", type=float, default=None,
                    help="per-request mean inter-token latency target "
                         "(virtual s)")
    ap.add_argument("--adaptive", action="store_true",
                    help="engine-side adaptive control: watermark AIMD on "
                         "the page pool, fluid-ODE admission gate, "
                         "per-victim swap-vs-recompute (needs --kv-layout "
                         "paged)")
    args = ap.parse_args()
    if args.cloud_batch and (args.preemption != "off"
                             or args.num_pages is not None):
        # multi-client mode runs one single-slot engine per client: a lone
        # slot has no victim to preempt, and generate_multi sizes its own
        # pools — fail loudly instead of silently ignoring the flags
        ap.error("--preemption/--num-pages apply to the single-engine "
                 "scheduler; drop --cloud-batch to use them")
    if args.kv_layout != "paged" and (args.preemption != "off"
                                      or args.num_pages is not None):
        # dense slots own fixed rings: there is no page pool to
        # oversubscribe, so these flags could never take effect
        ap.error("--preemption/--num-pages need --kv-layout paged")
    if args.kv_layout != "paged" and args.kv_dtype != "float32":
        ap.error("--kv-dtype int8 needs --kv-layout paged")
    if args.spec_k != 1 and not args.speculative:
        ap.error("--spec-k needs --speculative (drafting generalizes the "
                 "speculative path)")
    if args.chunked_prefill and args.kv_layout != "paged":
        ap.error("--chunked-prefill writes chunks through the paged "
                 "decode path; needs --kv-layout paged")
    if args.prefix_share and not args.chunked_prefill:
        ap.error("--prefix-share admits the unshared suffix through "
                 "chunked prefill; needs --chunked-prefill")
    if args.cloud_dp != 1 and not args.cloud_tp:
        ap.error("--cloud-dp sizes the data axis of the cloud mesh; "
                 "needs --cloud-tp")
    if args.arrival_rate < 0:
        ap.error("--arrival-rate must be >= 0")
    if args.arrival_rate == 0 and (args.arrival_cv2 != 1.0
                                   or args.diurnal_amp != 0.0):
        ap.error("--arrival-cv2/--diurnal-amp shape the arrival process; "
                 "need --arrival-rate > 0")
    if ((args.arrival_rate > 0 or args.slo_ttft is not None
         or args.slo_tpot is not None) and args.channel != "sim"):
        ap.error("--arrival-rate/--slo-* replay in virtual time; need "
                 "--channel sim")
    if args.adaptive and args.kv_layout != "paged":
        ap.error("--adaptive tunes the paged pool's watermark and "
                 "admission; needs --kv-layout paged")
    if args.adaptive and args.cloud_batch:
        ap.error("--adaptive drives the single-engine scheduler; drop "
                 "--cloud-batch to use it")
    cloud_mesh = (args.cloud_dp, args.cloud_tp) if args.cloud_tp else None
    if cloud_mesh is not None:
        need = cloud_mesh[0] * cloud_mesh[1]
        if need > len(jax.devices()):
            ap.error(f"--cloud-dp x --cloud-tp = {need} devices but only "
                     f"{len(jax.devices())} visible (locally: export "
                     f"XLA_FLAGS=--xla_force_host_platform_device_count="
                     f"{need} before launching)")

    enable_compile_cache()
    model = served_model(args.arch, args.smoke)
    cfg = model.cfg
    data = SyntheticCorpus(DataConfig(vocab_size=cfg.vocab_size, seq_len=64,
                                      batch_size=1))
    ccfg = CollmConfig(
        theta=args.theta, wire_format=args.wire, backfill=args.backfill,
        speculative=args.speculative, spec_k=args.spec_k,
        kv_layout=args.kv_layout, kv_dtype=args.kv_dtype,
        preemption=args.preemption, preempt_policy=args.preempt_policy,
        chunked_prefill=args.chunked_prefill,
        prefix_share=args.prefix_share,
        cloud_mesh=cloud_mesh)
    params = init_params(model, ccfg)
    if args.ckpt:
        params, _ = load_checkpoint(args.ckpt, params)
    prompts = [data.sample_tokens(args.prompt_len)
               for _ in range(args.clients)]
    if args.prefix_share:
        # the workload the flag exists for: every client opens with the
        # same system prompt (2.5 KV pages of it, so full pages can be
        # shared and the partial tail exercises copy-on-write), then its
        # own request
        import numpy as np
        system_prefix = data.sample_tokens(2 * ccfg.page_size
                                           + ccfg.page_size // 2)
        prompts = [np.concatenate([system_prefix, p]).astype(p.dtype)
                   for p in prompts]
    arrivals = None
    if args.arrival_rate > 0:
        proc = ArrivalProcess(
            rate=args.arrival_rate,
            kind="poisson" if args.arrival_cv2 == 1.0 else "gamma",
            cv2=args.arrival_cv2, diurnal_amp=args.diurnal_amp,
            diurnal_period_s=args.diurnal_period)
        arrivals = arrival_times(proc, len(prompts),
                                 seed=args.arrival_seed)
    fleet_kw = {}
    if arrivals is not None:
        fleet_kw["arrivals"] = arrivals
    if args.slo_ttft is not None:
        fleet_kw["slo_ttft_s"] = args.slo_ttft
    if args.slo_tpot is not None:
        fleet_kw["slo_tpot_s"] = args.slo_tpot
    system = ServingSystem(model, params, ccfg)
    if args.cloud_batch:
        gen_kw = dict(fleet_kw)
        if args.channel == "sim":
            # a single client has nobody to coalesce with: plain FIFO
            svc = CloudServicePoint(
                args.service_s,
                batch_window_s=args.batch_window if args.clients > 1 else 0.0,
                max_batch=args.clients)
            gen_kw.update(
                channels=[AsyncSimChannel(NetworkParams(),
                                          deadline_s=args.deadline,
                                          service=svc)
                          for _ in range(args.clients)],
                tick_time_s=args.tick_time)
        r = system.generate_multi(prompts, args.max_new, mode=args.mode,
                                  cloud_batch=True, **gen_kw)
        if "batcher" in r:
            print(f"cloud batcher: {r['batcher']}")
    else:
        gen_kw = dict(fleet_kw)
        if args.channel == "sim":
            gen_kw.update(channel=AsyncSimChannel(NetworkParams(),
                                                  deadline_s=args.deadline),
                          tick_time_s=args.tick_time)
        if args.num_pages is not None:
            gen_kw["num_pages"] = args.num_pages
        if args.adaptive:
            # per-victim swap-vs-recompute needs the cost model; both the
            # watermark AIMD and the admission gate hang off the config
            gen_kw.update(adaptive=AdaptiveConfig(),
                          resume_cost=ResumeCostModel())
        r = system.generate(prompts, args.max_new, mode=args.mode, **gen_kw)
    st = r["stats"]
    print(f"mode={args.mode} theta={args.theta} wire={args.wire} "
          f"channel={args.channel} cloud_batch={args.cloud_batch}")
    if cloud_mesh is not None:
        print(f"cloud mesh: data={cloud_mesh[0]} model={cloud_mesh[1]} "
              f"({cloud_mesh[0] * cloud_mesh[1]} devices)")
    print(f"tokens={st.tokens} exits@l1={st.exits_l1} exits@l2={st.exits_l2} "
          f"cloud_requests={st.cloud_requests} "
          f"request_rate={st.request_rate:.2%}")
    print(f"upload={st.upload_bytes/1e3:.1f}KB")
    if model.cfg.moe is not None and args.mode != "cloud":
        print(f"held_expert_tokens={st.held_expert_tokens} (token, expert) "
              f"pairs on held experts over the edge's moe layers")
    print("phases (calls/host s): " + " ".join(
        f"{name.removeprefix('engine.')}={v['calls']}/{v['seconds']:.3f}"
        for name, v in r["phases"].items()))
    if args.preemption != "off":
        print(f"preemptions={st.preemptions} policy={args.preempt_policy} "
              f"mode={args.preemption}")
    if args.chunked_prefill:
        print(f"prefill_chunks={st.prefill_chunks} "
              f"prefix_hit_tokens={st.prefix_hit_tokens} "
              f"cow_copies={st.cow_copies}")
    if args.speculative and st.draft_tokens:
        print(f"draft: k={args.spec_k} draft_tokens={st.draft_tokens} "
              f"accepted={st.accepted_tokens} "
              f"accept_rate={st.accepted_tokens / st.draft_tokens:.2%} "
              f"rewinds={st.spec_rewinds}")
    if args.channel == "sim":
        print(f"virtual_t={r['virtual_time']:.3f}s "
              f"deadline_misses={st.deadline_misses} "
              f"fallbacks={st.fallbacks} stall={st.stall_s:.3f}s "
              f"overlap={st.overlap_s:.3f}s late_drops={r['late_drops']}")
    if fleet_kw:
        print(f"fleet: ttft_p50={st.ttft_p(50) * 1e3:.1f}ms "
              f"ttft_p99={st.ttft_p(99) * 1e3:.1f}ms "
              f"tpot_p50={st.token_lat_p(50) * 1e3:.1f}ms "
              f"tpot_p99={st.token_lat_p(99) * 1e3:.1f}ms "
              f"slo={st.slo_attainment:.2%} ({st.slo_met}/{st.slo_total}) "
              f"preempt_rate={st.preemption_rate:.3f} "
              f"miss_rate={st.deadline_miss_rate:.3f}")
    if args.adaptive and r.get("adaptive") is not None:
        print(f"adaptive: {r['adaptive']}")
    if args.mode != "cloud":
        base_sys = system
        if args.chunked_prefill:
            # chunked prefill is edge-resident; the cloud baseline runs on
            # a plain config (same params, same greedy streams)
            base_sys = ServingSystem(model, params, CollmConfig(
                theta=args.theta, wire_format=args.wire,
                kv_layout=args.kv_layout, kv_dtype=args.kv_dtype))
        base = base_sys.generate(prompts, args.max_new, mode="cloud")
        ags = [token_agreement(a, b)
               for a, b in zip(r["tokens"], base["tokens"])]
        print(f"agreement vs cloud (LCS-F1): "
              f"{[round(a, 3) for a in ags]}")
    print("content manager:", r["cm_stats"])


if __name__ == "__main__":
    main()
