"""Serving launcher: load (or init) params for an arch and run the batched
decode engine over a synthetic request stream.

    PYTHONPATH=src python -m repro.launch.serve --arch musicgen-large \
        --smoke --requests 8

``--mode continuous`` (default) uses per-slot admission with chunked
prefill; ``--mode wave`` runs the legacy lockstep baseline.

``--tp N`` shards each engine replica over N devices (tensor parallel —
mesh ``(1, N)``); ``--mesh-shape D,M`` (or ``P,D,M``) gives the full
device mesh, with the leading data axes sharding the decode slots and
splitting the paged KV pool into per-host sub-pools.  Sharded output is
bitwise-identical to the single-device engine — see docs/serving.md.

``--policy fcfs|priority|sjf|drf-fair`` picks the admission policy;
``--tenants N`` spreads the synthetic requests round-robin over N tenants
(tenant-0..tenant-N-1) so ``drf-fair`` has shares to balance.
``--temperature/--top-k/--top-p/--seed`` set the per-request sampling
params (temperature 0 = greedy).

``--cache paged`` swaps the dense per-slot KV stripes for the paged pool
(``--page-size``, ``--num-pages``, ``--page-policy pack|spread``,
``--no-prefix-cache``); admission then reserves only the pages a request
can touch and queues with backpressure when the pool is exhausted.

``--preempt`` enables Mesos-style slot revocation (checkpoint/restore;
``--victim-policy youngest-first|lowest-weight-share-first``), and
``--tenant-weights "tenant-0=3,tenant-1=1"`` maps SLO tiers onto
weighted-DRF shares.

``--speculate`` enables speculative multi-token decode (``--draft-k N``
tokens per slot per tick, ``--drafter`` from ``runtime.draft.DRAFTERS``);
the run reports the draft acceptance rate alongside throughput.

``--trace-out PATH`` records the full run as Chrome trace-event JSON
(open it at https://ui.perfetto.dev); ``--metrics-out PATH`` writes the
final metrics snapshot (``.prom`` = Prometheus text, else JSON);
``--flight-recorder N`` arms a bounded flight recorder whose last N
trace events + metrics are dumped to ``artifacts/`` automatically on a
replica fence.  See docs/observability.md.

``--replicas N`` (N > 1, or any ``--fault-schedule``) fronts N engine
replicas with a ``runtime.cluster.ClusterRouter``: requests are placed
via ``--router-policy pack|spread`` offers, lost replicas are detected by
heartbeat (``--miss-threshold``) and their in-flight requests recovered
by deterministic replay on the survivors (``--retry-budget`` replays per
request).  ``--fault-schedule`` injects reproducible chaos — either
explicit ``TICK:ACTION:REPLICA[:ARG[:TICKS]]`` entries (e.g.
``"8:kill:1,30:rejoin:1"``) or ``"seed=SEED"`` for a generated schedule;
the run asserts zero lost requests.

``--roles "prefill=N,decode=M[,unified=K]"`` splits the replica pool by
role (counts must sum to ``--replicas``): a ``runtime.disagg``
``DisaggRouter`` places fresh requests on prefill workers and hands
finished prefills' KV chains off to decode slots.  ``--autoscale-policy
queue-depth|slo-backlog`` attaches an elastic ``runtime.autoscale``
``Autoscaler`` (``--min-replicas``/``--max-replicas`` per-role bounds,
``--scale-cooldown`` anti-flap freeze); ``--max-replicas`` above a
role's initial count provisions cold DOWN spares for scale-up to
rejoin.  See docs/disagg_autoscale.md.

The runtime knobs come from the platform (``serving_knobs``): bf16 with
the Pallas kernels on a TPU, bf16 on XLA attention when a mesh is set,
float32 XLA elsewhere.  JAX's persistent compilation cache goes to
``$JAX_COMPILATION_CACHE_DIR`` when set, else to ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, list_archs
from repro.launch.mesh import make_serve_mesh
from repro.launch.pool_fit import fit_device_pool
from repro.models import LM, RuntimeKnobs
from repro.runtime.autoscale import AUTOSCALE_POLICIES, Autoscaler
from repro.runtime.cluster import ROUTER_POLICIES, ClusterRouter
from repro.runtime.disagg import ROLES, DisaggRouter
from repro.runtime.draft import DRAFTERS
from repro.runtime.fault import ReplicaFaultInjector
from repro.runtime.scheduler import ADMISSION_POLICIES, VICTIM_POLICIES
from repro.runtime.serve import (Request, SamplingParams, ServeConfig,
                                 ServeEngine)
from repro.runtime.telemetry import Telemetry
from repro.sharding import serve_param_shardings

REPO_ROOT = Path(__file__).resolve().parents[3]


def serving_knobs(backend: str, *, sharded: bool) -> RuntimeKnobs:
    """The serving knobs for what this process runs on.

    * ``backend == "tpu"``, one device: bf16 params, compute and KV cache
      with the Pallas kernels.
    * ``backend == "tpu"`` with a mesh: bf16 on XLA attention — the Pallas
      kernels are single-device and ``ServeEngine`` refuses them there.
    * any other backend (the CPU tests): float32 params, compute and KV
      cache on XLA, which keeps cache round trips bit-exact.
    """
    if backend != "tpu":
        return RuntimeKnobs(cache_dtype=jnp.float32)
    return RuntimeKnobs(param_dtype=jnp.bfloat16, compute_dtype=jnp.bfloat16,
                        cache_dtype=jnp.bfloat16, use_pallas=not sharded)


def build_serving_model(cfg, *, mesh_shape=None):
    """``(model, params)`` as the launcher serves them: knobs from
    ``serving_knobs`` for ``jax.default_backend()``, params drawn on the
    device from seed 0.  With ``mesh_shape`` the params are created in
    the sharded engine's layout (``serve_param_shardings``), so no device
    ever holds a full unsharded copy."""
    sharded = mesh_shape is not None
    model = LM(cfg, serving_knobs(jax.default_backend(), sharded=sharded))
    out_shardings = None
    if sharded:
        out_shardings = serve_param_shardings(
            make_serve_mesh(mesh_shape), cfg, model.param_specs())
    init = jax.jit(model.init, out_shardings=out_shardings)
    return model, init(jax.random.PRNGKey(0))


def fitted_num_pages(model, serve_cfg: ServeConfig):
    """The page pool one engine asks for when ``--num-pages`` is not
    given.  For a paged engine on one TPU: the largest pool whose compiled
    steps fit the device (``pool_fit.fit_device_pool``, as
    ``chip_smoke.py`` sizes it).  Elsewhere ``serve_cfg.num_pages``
    unchanged (``None`` is the engine's dense-equivalent default)."""
    if (serve_cfg.num_pages is not None or serve_cfg.cache != "paged"
            or serve_cfg.mesh_shape is not None
            or jax.default_backend() != "tpu"):
        return serve_cfg.num_pages
    if serve_cfg.kv_dtype:
        model = LM(model.cfg, model.knobs.with_(kv_quant=serve_cfg.kv_dtype))
    fit = fit_device_pool(model, jax.devices()[0],
                          slots=serve_cfg.batch_slots,
                          max_len=serve_cfg.max_len,
                          page_size=serve_cfg.page_size,
                          chunk=serve_cfg.prefill_chunk)
    return fit.num_pages


def enable_compile_cache(root=REPO_ROOT) -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory: ``$JAX_COMPILATION_CACHE_DIR`` when set (JAX reads the
    variable itself), else the fixed ``<root>/.jax_cache``.  Call at
    program start, before the first compile: JAX decides once whether a
    process uses the cache."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(Path(root) / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def parse_tenant_weights(spec: str) -> dict:
    """``"gold=3,free=1"`` -> ``{"gold": 3.0, "free": 1.0}``.  Raises
    ``ValueError`` (an argparse usage error) on malformed entries or
    non-positive weights, so bad configs fail at the CLI instead of as
    an assertion deep inside scheduling."""
    out = {}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        name, eq, w = part.partition("=")
        name = name.strip()
        if not eq or not name:
            raise ValueError(f"expected TENANT=WEIGHT, got {part!r}")
        weight = float(w)  # ValueError on junk -> argparse usage error
        if weight <= 0:
            raise ValueError(f"weight for {name!r} must be > 0, "
                             f"got {weight}")
        out[name] = weight
    return out


def parse_roles(spec: str) -> dict:
    """``"prefill=2,decode=1"`` -> ``{"prefill": 2, "decode": 1}``.
    Raises ``ValueError`` (an argparse usage error) on unknown roles,
    duplicates, or non-positive counts."""
    out = {}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        role, eq, n = part.partition("=")
        role = role.strip()
        if not eq or role not in ROLES:
            raise ValueError(f"expected ROLE=COUNT with ROLE in "
                             f"{'/'.join(ROLES)}, got {part!r}")
        if role in out:
            raise ValueError(f"role {role!r} listed twice")
        count = int(n)  # ValueError on junk -> argparse usage error
        if count <= 0:
            raise ValueError(f"count for {role!r} must be > 0, "
                             f"got {count}")
        out[role] = count
    if not out:
        raise ValueError("empty --roles spec")
    return out


def parse_mesh_shape(spec: str) -> tuple:
    """``"2,4"`` -> ``(2, 4)``: a (data, model) or (pod, data, model)
    device-mesh shape.  Raises ``ValueError`` (an argparse usage error)
    on junk so bad shapes fail at the CLI, not at engine construction."""
    try:
        shape = tuple(int(p) for p in spec.split(","))
    except ValueError:
        raise ValueError(f"expected comma-separated ints, got {spec!r}")
    if len(shape) not in (2, 3) or any(s < 1 for s in shape):
        raise ValueError(f"mesh shape must be D,M or P,D,M of positive "
                         f"ints, got {spec!r}")
    return shape


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list_archs())
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--mode", choices=("continuous", "wave"),
                    default="continuous")
    ap.add_argument("--tp", type=int, default=1,
                    help="shard each replica over N devices (tensor "
                         "parallel; shorthand for --mesh-shape 1,N)")
    ap.add_argument("--mesh-shape", type=parse_mesh_shape, default=None,
                    metavar="D,M",
                    help="per-replica device mesh 'data,model' (or "
                         "'pod,data,model'); data axes shard the decode "
                         "slots + KV page pool across hosts")
    ap.add_argument("--prefill-chunk", type=int, default=32)
    ap.add_argument("--policy", choices=sorted(ADMISSION_POLICIES),
                    default="fcfs", help="admission policy")
    ap.add_argument("--tenants", type=int, default=1,
                    help="spread requests over N tenants (round-robin)")
    ap.add_argument("--tenant-weights", type=parse_tenant_weights,
                    default=None, metavar="T=W,...",
                    help="weighted-DRF SLO tiers, e.g. 'tenant-0=3,"
                         "tenant-1=1' (unlisted tenants weigh 1)")
    ap.add_argument("--preempt", action="store_true",
                    help="enable slot preemption (checkpoint/restore)")
    ap.add_argument("--victim-policy", choices=sorted(VICTIM_POLICIES),
                    default="youngest-first")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature (0 = greedy)")
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--top-p", type=float, default=1.0)
    ap.add_argument("--seed", type=int, default=None,
                    help="per-request sampling seed (default: request id)")
    ap.add_argument("--speculate", action="store_true",
                    help="speculative multi-token decode (see --draft-k)")
    ap.add_argument("--draft-k", type=int, default=3,
                    help="draft tokens per slot per tick (with --speculate)")
    ap.add_argument("--drafter", choices=sorted(DRAFTERS), default="ngram")
    ap.add_argument("--cache", choices=("dense", "paged"), default="dense")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--num-pages", type=int, default=None,
                    help="pool size (default: dense-equivalent capacity)")
    ap.add_argument("--page-policy", choices=("pack", "spread"),
                    default="pack")
    ap.add_argument("--kv-dtype", choices=("", "int8", "fp8"), default="",
                    help="quantize the paged KV pool (per-token scales, "
                         "dequantized in-kernel); needs --cache paged")
    ap.add_argument("--no-prefix-cache", action="store_true")
    ap.add_argument("--replicas", type=int, default=1,
                    help="front N engine replicas with a ClusterRouter")
    ap.add_argument("--router-policy", choices=sorted(ROUTER_POLICIES),
                    default="spread",
                    help="replica placement policy (with --replicas > 1)")
    ap.add_argument("--roles", type=parse_roles, default=None,
                    metavar="ROLE=N,...",
                    help="disaggregate the pool: 'prefill=N,decode=M"
                         "[,unified=K]' (counts must sum to --replicas)")
    ap.add_argument("--autoscale-policy",
                    choices=sorted(AUTOSCALE_POLICIES), default=None,
                    help="attach an elastic autoscaler (needs --roles)")
    ap.add_argument("--min-replicas", type=int, default=None,
                    help="per-role floor for scale-down (default 1)")
    ap.add_argument("--max-replicas", type=int, default=None,
                    help="per-role ceiling; above a role's initial count "
                         "this provisions cold spares for scale-up")
    ap.add_argument("--scale-cooldown", type=int, default=None,
                    help="ticks a role is frozen after a scale event "
                         "(default 10)")
    ap.add_argument("--fault-schedule", default=None,
                    metavar="T:ACT:R[,...]|seed=N",
                    help="inject chaos: 'TICK:ACTION:REPLICA[:ARG[:TICKS]]"
                         ",...' or 'seed=SEED' (forces the router path)")
    ap.add_argument("--miss-threshold", type=int, default=3,
                    help="heartbeat misses before a replica is LOST")
    ap.add_argument("--retry-budget", type=int, default=3,
                    help="recovery replays per request before it fails")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write the run's Chrome trace-event JSON here "
                         "(Perfetto-viewable)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write the final metrics snapshot here "
                         "(.prom = Prometheus text, else JSON)")
    ap.add_argument("--flight-recorder", type=int, default=0, metavar="N",
                    help="arm the flight recorder: dump the last N trace "
                         "events + metrics to artifacts/ on replica fence")
    args = ap.parse_args()
    if args.tp < 1:
        ap.error(f"--tp must be >= 1 (got {args.tp})")
    if args.tp > 1 and args.mesh_shape is not None:
        ap.error("--tp is shorthand for --mesh-shape 1,N — pass one "
                 "or the other")
    mesh_shape = (args.mesh_shape if args.mesh_shape is not None
                  else ((1, args.tp) if args.tp > 1 else None))
    if mesh_shape is not None and args.mode != "continuous":
        ap.error(f"--mesh-shape/--tp need --mode continuous "
                 f"(got {args.mode!r})")
    if args.speculate and args.draft_k <= 0:
        ap.error(f"--speculate needs --draft-k >= 1 (got {args.draft_k})")
    if args.kv_dtype and args.cache != "paged":
        ap.error(f"--kv-dtype {args.kv_dtype} needs --cache paged")
    if args.replicas < 1:
        ap.error(f"--replicas must be >= 1 (got {args.replicas})")
    if args.roles is not None:
        total = sum(args.roles.values())
        if total != args.replicas:
            ap.error(f"--roles counts sum to {total} but --replicas is "
                     f"{args.replicas} — pass --replicas {total}")
        have = set(args.roles)
        if not have & {"prefill", "unified"}:
            ap.error("--roles needs a prefill-capable role "
                     "(prefill or unified)")
        if not have & {"decode", "unified"}:
            ap.error("--roles needs a decode-capable role "
                     "(decode or unified)")
        if args.mode != "continuous":
            ap.error(f"--roles needs --mode continuous "
                     f"(got {args.mode!r})")
    elif args.autoscale_policy is not None:
        ap.error("--autoscale-policy needs --roles")
    if args.autoscale_policy is None:
        for flag, val in (("--min-replicas", args.min_replicas),
                          ("--max-replicas", args.max_replicas),
                          ("--scale-cooldown", args.scale_cooldown)):
            if val is not None:
                ap.error(f"{flag} needs --autoscale-policy")
    else:
        min_r = 1 if args.min_replicas is None else args.min_replicas
        if min_r < 1:
            ap.error(f"--min-replicas must be >= 1 (got {min_r})")
        if min_r > min(args.roles.values()):
            ap.error(f"--min-replicas {min_r} exceeds the smallest "
                     f"initial role count {min(args.roles.values())}")
        if (args.max_replicas is not None
                and args.max_replicas < max(args.roles.values())):
            ap.error(f"--max-replicas {args.max_replicas} is below the "
                     f"largest initial role count "
                     f"{max(args.roles.values())}")
        if args.scale_cooldown is not None and args.scale_cooldown < 0:
            ap.error(f"--scale-cooldown must be >= 0 "
                     f"(got {args.scale_cooldown})")

    enable_compile_cache()
    cfg = get_config(args.arch, smoke=args.smoke)
    model, params = build_serving_model(cfg, mesh_shape=mesh_shape)
    serve_cfg = ServeConfig(
        batch_slots=args.slots, max_len=args.max_len, mode=args.mode,
        prefill_chunk=args.prefill_chunk, cache=args.cache,
        page_size=args.page_size, num_pages=args.num_pages,
        page_policy=args.page_policy, kv_dtype=args.kv_dtype,
        prefix_cache=not args.no_prefix_cache, policy=args.policy,
        tenant_weights=args.tenant_weights, preempt=args.preempt,
        victim_policy=args.victim_policy,
        draft_k=args.draft_k if args.speculate else 0,
        drafter=args.drafter, mesh_shape=mesh_shape)
    if args.roles is None and args.replicas == 1:
        # replicas would each hold a pool on the same device (ROADMAP R6)
        num_pages = fitted_num_pages(model, serve_cfg)
        if num_pages != serve_cfg.num_pages:
            print(f"paged pool fitted to the device: {num_pages} pages")
            serve_cfg = dataclasses.replace(serve_cfg, num_pages=num_pages)

    tm = Telemetry(trace=bool(args.trace_out) or args.flight_recorder > 0,
                   flight=args.flight_recorder, flight_dir="artifacts")

    # replicas share model/params; compiled steps dedupe via runtime.steps
    def make_engine(rid):
        return ServeEngine(model, params, serve_cfg)

    router = None
    if args.roles is not None:
        # role list rid-by-rid; indices past a role's initial count are
        # cold DOWN spares the autoscaler can rejoin under load
        cap = (args.max_replicas if args.autoscale_policy
               and args.max_replicas is not None else None)
        role_list, start_down = [], []
        for role, count in args.roles.items():
            for i in range(max(count, cap or 0)):
                if i >= count:
                    start_down.append(len(role_list))
                role_list.append(role)

        def make_role_engine(rid):
            return ServeEngine(model, params, dataclasses.replace(
                serve_cfg, role=role_list[rid]))

        injector = (ReplicaFaultInjector.parse(args.fault_schedule)
                    if args.fault_schedule else None)
        router = DisaggRouter(make_role_engine, len(role_list),
                              roles=role_list, start_down=start_down,
                              policy=args.router_policy,
                              miss_threshold=args.miss_threshold,
                              retry_budget=args.retry_budget,
                              tenant_weights=args.tenant_weights or {},
                              injector=injector, telemetry=tm)
        if args.autoscale_policy:
            router.autoscaler = Autoscaler(
                router, args.autoscale_policy,
                min_replicas=(1 if args.min_replicas is None
                              else args.min_replicas),
                max_replicas=cap,
                cooldown=(10 if args.scale_cooldown is None
                          else args.scale_cooldown),
                telemetry=tm)
    elif args.replicas > 1 or args.fault_schedule:
        injector = (ReplicaFaultInjector.parse(args.fault_schedule)
                    if args.fault_schedule else None)
        router = ClusterRouter(make_engine, args.replicas,
                               policy=args.router_policy,
                               miss_threshold=args.miss_threshold,
                               retry_budget=args.retry_budget,
                               tenant_weights=args.tenant_weights or {},
                               injector=injector, telemetry=tm)
    else:
        engine = make_engine(0)
        engine.bind_telemetry(tm, replica=0)
    sampling = SamplingParams(temperature=args.temperature,
                              top_k=args.top_k, top_p=args.top_p,
                              seed=args.seed)
    rng = np.random.default_rng(0)
    handles = []
    front = router if router is not None else engine
    for i in range(args.requests):
        plen = int(rng.integers(1, 6))
        handles.append(front.submit(Request(
            i, rng.integers(0, cfg.vocab_size, size=plen).astype(np.int32),
            max_new_tokens=args.max_new, sampling=sampling,
            tenant=f"tenant-{i % max(args.tenants, 1)}",
            priority=i % 3)))
    t0 = time.time()
    done = front.run()
    dt = time.time() - t0
    toks = sum(len(r.output) for r in done)
    ttft = [h.metrics().get("ttft_s") for h in handles]
    ttft = [t for t in ttft if t is not None]
    mesh_note = (f" mesh={'x'.join(map(str, mesh_shape))}"
                 if mesh_shape else "")
    print(f"arch={args.arch} mode={args.mode} cache={args.cache} "
          f"policy={args.policy}{mesh_note} served {len(done)} requests, "
          f"{toks} tokens in {dt:.1f}s ({toks / max(dt, 1e-9):.1f} tok/s)")
    if router is not None:
        st = router.stats()
        print(f"cluster: replicas={args.replicas} "
              f"router-policy={args.router_policy} ticks={st['ticks']} "
              f"lost={st['replicas_lost']} recoveries={st['recoveries']} "
              f"brownout-ticks={st['brownout_ticks']}")
        lost = [r.req_id for r in done if r.finish_reason == "failed"]
        assert not lost, f"requests lost despite recovery: {lost}"
        if args.roles is not None:
            print(f"disagg: roles={{{','.join(f'{r}={n}' for r, n in args.roles.items())}}} "
                  f"handoffs={st['handoffs_done']} "
                  f"backpressure={st['handoff_backpressure']} "
                  f"in-transit={st['handoffs_in_transit']}")
        if getattr(router, "autoscaler", None) is not None:
            asst = router.autoscaler.stats()
            print(f"autoscale: policy={asst['policy']} "
                  f"ups={asst['scale_ups']} downs={asst['scale_downs']} "
                  f"retiring={asst['retiring']}")
    if args.preempt and router is None:
        print(f"preemptions: {engine.scheduler.preempted_total} "
              f"(requests preempted >=1x: "
              f"{sum(1 for r in done if r.preempt_count)})")
    if args.speculate and router is None:
        st = engine.spec_stats()
        print(f"speculative: draft_k={st['draft_k']} "
              f"acceptance {st['acceptance_rate']:.2f} "
              f"({st['accepted']}/{st['proposed']}), "
              f"{st['tokens_per_tick']:.2f} tok/tick")
    if ttft:
        print(f"ttft p50 {np.percentile(ttft, 50) * 1e3:.0f}ms / "
              f"p99 {np.percentile(ttft, 99) * 1e3:.0f}ms "
              f"(finish reasons: "
              f"{sorted({r.finish_reason for r in done})})")
    if args.cache == "paged" and router is None:
        print(f"kv stats: {engine.kv_stats()}")
    if args.trace_out:
        path = tm.write_trace(args.trace_out)
        tr = tm.trace
        print(f"trace: {tr.total} events ({tr.dropped} dropped) -> {path} "
              f"(open at https://ui.perfetto.dev)")
    if args.metrics_out:
        print(f"metrics: {len(tm.registry.names())} series -> "
              f"{tm.write_metrics(args.metrics_out)}")
    if tm.flight_dumps:
        print(f"flight-recorder dumps: {tm.flight_dumps}")


if __name__ == "__main__":
    main()
