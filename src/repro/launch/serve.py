"""Sharded serving driver (production entry point).

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3_1_7b --reduced \
      --devices 4 --dp 2 --tp 2 --requests 8

Serving a deployment artifact (the export -> load -> serve flow; the
prune/tune session that produced it need not exist in this process):

  PYTHONPATH=src python -m repro.launch.serve --artifact path/to/artifact

Serving a whole catalog (Plan.export_catalog output) through the
SLO-aware router — per-request latency budgets dispatch to the cheapest
satisfying frontier artifact:

  PYTHONPATH=src python -m repro.launch.serve --catalog path/to/fleet \
      --budget-ms 5,50 --requests 16

Fault-tolerant fleet serving: ``--replicas N`` puts every entry behind a
ReplicaSupervisor (N engines, crash recovery, deadline-ordered bounded
intake), ``--max-queue``/``--retry-budget`` bound admission and
re-queues, and ``--chaos`` injects a deterministic failure mix (engine
crash mid-decode, a straggler tick) to demonstrate recovery:

  PYTHONPATH=src python -m repro.launch.serve --catalog path/to/fleet \
      --replicas 2 --max-queue 32 --retry-budget 3 --chaos

Autopilot serving: ``--autopilot`` puts the catalog router under the
online control plane — every ``--check-every`` steps it scores each
entry's predicted-vs-measured drift and budget-violation rate, and on a
threshold crossing it replans under the recalibrated replay oracle and
hot-swaps the new catalog generation in (new requests route on the new
generation, in-flight requests drain on the old engines; a worse
generation is rolled back after ``--probation-steps``):

  PYTHONPATH=src python -m repro.launch.serve --catalog path/to/fleet \
      --autopilot --budget-ms 5,50 --requests 16 --max-swaps 1
"""
import argparse
import os


def _early_env():
    ap = _parser()
    args, _ = ap.parse_known_args()
    if args.devices > 1:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices}")
    return args


def _parser():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3_1_7b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--artifact", default=None,
                    help="serve a DeploymentArtifact directory (overrides "
                         "--arch/--reduced; params, config, and the tuned "
                         "decode-step prediction all come from the artifact)")
    ap.add_argument("--catalog", default=None,
                    help="serve an ArtifactCatalog directory (a "
                         "Plan.export_catalog output) through the SLO "
                         "router; overrides --artifact/--arch")
    ap.add_argument("--budget-ms", default=None,
                    help="comma-separated per-request latency budgets in "
                         "ms, cycled over the synthetic requests "
                         "(catalog mode; e.g. '5,50')")
    ap.add_argument("--floor", type=float, default=None,
                    help="per-request accuracy floor (catalog mode)")
    ap.add_argument("--route-policy", default="quality",
                    choices=["quality", "cheapest"])
    ap.add_argument("--on-unroutable", default="flag",
                    choices=["reject", "flag"])
    ap.add_argument("--scheduler", default="bucketed",
                    choices=["bucketed", "fifo", "wave"],
                    help="engine admission policy (wave = the legacy "
                         "blocking drain, kept for comparison)")
    ap.add_argument("--record", default=None,
                    help="record the observed decode step into this "
                         "MeasurementLog JSON (feeds "
                         "DeploymentArtifact.recalibrated_oracle)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="supervised engine replicas per catalog entry "
                         "(or per artifact); >1 implies fleet serving")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="bound each entry's intake + in-flight; overflow "
                         "is shed with RouteError at submit")
    ap.add_argument("--retry-budget", type=int, default=2,
                    help="per-request re-queue budget after engine "
                         "crashes (beyond it the request fails "
                         "explicitly)")
    ap.add_argument("--chaos", action="store_true",
                    help="inject a deterministic failure mix (decode "
                         "crash + straggler) to demonstrate supervised "
                         "recovery")
    ap.add_argument("--autopilot", action="store_true",
                    help="catalog mode only: watch per-entry drift "
                         "(oracle_rel_error, budget_violation_rate, "
                         "crashes/quarantine), replan under the "
                         "recalibrated oracle, and hot-swap new catalog "
                         "generations with zero downtime")
    ap.add_argument("--check-every", type=int, default=16,
                    help="router steps between autopilot health sweeps")
    ap.add_argument("--rel-error-threshold", type=float, default=0.5,
                    help="windowed |measured-predicted|/predicted that "
                         "counts as oracle drift")
    ap.add_argument("--violation-threshold", type=float, default=0.5,
                    help="per-entry budget-violation rate that counts "
                         "as drift")
    ap.add_argument("--probation-steps", type=int, default=64,
                    help="router steps a freshly swapped generation "
                         "serves before it is judged (worse violation "
                         "rate than the outgoing generation -> rollback)")
    ap.add_argument("--cooldown-steps", type=int, default=64,
                    help="minimum router steps between replans "
                         "(a rollback quadruples it)")
    ap.add_argument("--keep-generations", type=int, default=3,
                    help="old catalog generations kept on disk after a "
                         "passed probation")
    ap.add_argument("--max-swaps", type=int, default=None,
                    help="hard cap on autonomous swaps (default: "
                         "unlimited)")
    ap.add_argument("--devices", type=int, default=1)
    ap.add_argument("--dp", type=int, default=1)
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel serving degree: >1 shards "
                         "params and KV over the mesh's model axis "
                         "(ShardedServeEngine); the mesh must supply tp "
                         "devices — errors name the shortfall")
    ap.add_argument("--mesh", default=None,
                    help="explicit (data, model) serving mesh as DxM "
                         "(e.g. 1x2); must agree with --tp and fit "
                         "--devices — mismatches raise MeshError naming "
                         "both shapes")
    ap.add_argument("--replicas-per-entry", type=int, default=None,
                    help="catalog mode: supervised engine replicas per "
                         "catalog entry (overrides --replicas there; "
                         "each replica of a tp>1 entry gets the full "
                         "sharded mesh)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    return ap


def _serving_mesh(args):
    """The (data, model) serving mesh implied by --mesh/--tp, or None
    for plain single-device serving. Every failure mode raises
    :class:`~repro.launch.mesh.MeshError` naming the shapes involved:
    a --mesh string whose model axis disagrees with --tp, or a mesh
    that needs more devices than --devices forced into existence."""
    if args.mesh is None and args.tp <= 1:
        return None
    from repro.launch.mesh import MeshError, make_test_mesh
    from repro.serve.distributed import validate_mesh
    if args.mesh is not None:
        try:
            data, model = (int(x) for x in args.mesh.lower().split("x"))
        except ValueError:
            raise SystemExit(
                f"--mesh must be DATAxMODEL (e.g. 1x2), got {args.mesh!r}")
        if args.tp > 1 and model != args.tp:
            raise MeshError(
                f"--mesh {args.mesh} has a model axis of {model} but "
                f"--tp {args.tp} asks for {args.tp} model shards — a "
                f"({data}, {model}) (data, model) mesh cannot serve "
                f"tp={args.tp}; pass --mesh {data}x{args.tp} or drop --tp")
        mesh = make_test_mesh(n_devices=data * model, model=model)
    else:
        mesh = make_test_mesh(n_devices=args.tp, model=args.tp)
    validate_mesh(mesh, tp=args.tp if args.tp > 1 else None,
                  what=f"--tp {args.tp}")
    return mesh


def synthetic_requests(args, cfg, budgets):
    """The launcher's seeded traffic: ``args.requests`` random prompts of
    ``args.prompt_len`` tokens, alternating greedy and temperature 0.8."""
    import numpy as np
    rng = np.random.default_rng(0)
    from repro.serve.engine import Request
    for i in range(args.requests):
        budget = budgets[i % len(budgets)] if budgets else None
        yield Request(
            rid=i,
            prompt=rng.integers(0, cfg.vocab_size,
                                size=args.prompt_len).astype(np.int32),
            max_new_tokens=args.max_new,
            temperature=0.0 if i % 2 == 0 else 0.8,
            latency_budget_s=budget,
            accuracy_floor=args.floor)


def _print_stats(stats, indent=""):
    for k, v in stats.items():
        if k == "per_artifact":
            for name, sub in v.items():
                print(f"{indent}[{name}]")
                _print_stats(sub, indent + "  ")
        elif k == "per_replica":
            for i, sub in enumerate(v):
                print(f"{indent}[replica {i}]")
                _print_stats(sub, indent + "  ")
        else:
            print(f"{indent}{k}: {v}")


def _catalog_replan(catalog):
    """Replan closure for a disk-loaded catalog (no in-process Plan to
    re-run): re-sweep the catalog's own strategy x target arms under the
    recalibrated oracle, scoring accuracy by parameter retention — the
    serve driver has no training data, so retention stands in for the
    eval hook; a real deployment drives the Autopilot through the Python
    API with its own TrainHooks instead."""
    from repro.api import CPruneConfig, TrainHooks, Workload, plan

    cfg = catalog.artifact(catalog.names[0]).cfg
    strategies = list(dict.fromkeys(e.strategy for e in catalog.entries))
    targets = list(dict.fromkeys(e.target for e in catalog.entries))

    def _count(p):
        import jax
        return sum(x.size for x in jax.tree_util.tree_leaves(p))

    def _replan(trigger, oracle):
        import jax

        from repro.models.model import init_params
        params = init_params(jax.random.PRNGKey(0), cfg)
        n0 = _count(params)
        hooks = TrainHooks(short_term_train=lambda p, s: p,
                           eval_acc=lambda p, s: _count(p) / n0)
        return plan(cfg, accuracy_floor=0.0, targets=targets,
                    strategies=strategies,
                    workload=Workload(tokens_global=8192), hooks=hooks,
                    params=params, pcfg=CPruneConfig(a_g=0.0, seq_len=64),
                    oracle=oracle)

    return _replan


def _chaos_injector():
    """The --chaos failure mix: one engine crash early in decode plus
    one straggler tick — deterministic, so every run demonstrates a
    contained crash, a cold rebuild, and a re-queue."""
    from repro.util.faults import FaultInjector, crash_at, delay_at
    return FaultInjector(specs=[crash_at("decode", 3),
                                delay_at("decode", 0.05, 10)])


def main():
    args = _early_env()
    import jax

    from repro.configs import get_config, get_reduced_config
    from repro.core.oracle import MeasurementLog
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models.model import init_params
    from repro.serve.engine import ServeEngine

    enable_compile_cache()

    log = MeasurementLog() if args.record else None
    budgets = [float(b) * 1e-3 for b in args.budget_ms.split(",")] \
        if args.budget_ms else None

    mesh = _serving_mesh(args)
    if mesh is not None:
        print(f"serving mesh: "
              f"{dict((k, int(v)) for k, v in dict(mesh.shape).items())} "
              f"(tp={int(dict(mesh.shape)['model'])})")

    faults = _chaos_injector() if args.chaos else None
    retry = None
    if args.retry_budget != 2 or args.chaos:
        from repro.serve.fleet import RetryPolicy
        retry = RetryPolicy(max_retries=args.retry_budget)

    if args.catalog:
        from repro.serve.fleet import RouteError
        from repro.serve.router import ArtifactCatalog, Router
        # fleet serving loads lazily: a broken member is quarantined at
        # its engine-build time instead of refusing the whole catalog
        catalog = ArtifactCatalog.load(args.catalog, lazy=True)
        print(f"routing over catalog {args.catalog}:\n{catalog.summary()}")
        router = Router(catalog, policy=args.route_policy,
                        on_unroutable=args.on_unroutable,
                        scheduler=args.scheduler, measurements=log,
                        replicas=args.replicas_per_entry or args.replicas,
                        max_queue=args.max_queue,
                        retry=retry, faults=faults, mesh=mesh)
        cfg = catalog.artifact(catalog.names[0]).cfg
        pilot = None
        if args.autopilot:
            from repro.serve.autopilot import Autopilot, AutopilotConfig
            acfg = AutopilotConfig(
                check_every=args.check_every,
                rel_error_threshold=args.rel_error_threshold,
                violation_threshold=args.violation_threshold,
                probation_steps=args.probation_steps,
                cooldown_steps=args.cooldown_steps,
                keep_generations=args.keep_generations,
                max_swaps=args.max_swaps)
            pilot = Autopilot(router, replan=_catalog_replan(catalog),
                              config=acfg, log=log, faults=faults)
            print(f"autopilot on: check_every={acfg.check_every} "
                  f"rel_error>{acfg.rel_error_threshold} "
                  f"violation_rate>{acfg.violation_threshold} "
                  f"probation={acfg.probation_steps} "
                  f"keep={acfg.keep_generations} generations")
        shed = 0
        for req in synthetic_requests(args, cfg, budgets):
            try:
                router.submit(req)
            except RouteError as e:
                shed += 1
                print(f"shed: {e}")
        if pilot is not None:
            pstats = pilot.run()
            stats = router.stats()
            print("autopilot:")
            _print_stats(pstats, "  ")
        else:
            stats = router.run()
        _print_stats(stats)
        if shed:
            print(f"shed_at_submit: {shed}")
        if stats["quarantined"]:
            print(f"quarantined entries: {stats['quarantined']}")
        if log is not None:
            log.save(args.record)
            print(f"recorded {len(log)} measurement(s) -> {args.record}")
        return

    art = None
    if args.artifact:
        from repro.api.artifact import DeploymentArtifact
        art = DeploymentArtifact.load(args.artifact)
        cfg = art.cfg
    else:
        cfg = (get_reduced_config if args.reduced else get_config)(args.arch)
    if cfg.is_encoder_only:
        raise SystemExit("encoder-only arch has no decode step")
    if art is not None and (args.replicas > 1 or args.chaos):
        # supervised fleet over one artifact: crash recovery + re-queue
        from repro.serve.fleet import ReplicaSupervisor
        sup = ReplicaSupervisor.from_artifact(
            art, replicas=args.replicas, name=art.cfg.name,
            faults=faults, retry=retry, max_queue=args.max_queue,
            engine_kwargs=dict(max_batch=min(8, args.requests),
                               max_seq=args.prompt_len + args.max_new,
                               scheduler=args.scheduler, measurements=log,
                               **({"mesh": mesh}
                                  if mesh is not None else {})))
        print(f"supervising {args.replicas} replica(s) of {args.artifact} "
              f"(model={cfg.name}, chaos={'on' if args.chaos else 'off'})")
        for req in synthetic_requests(args, cfg, budgets):
            sup.submit(req)
        _print_stats(sup.run())
        if log is not None:
            for eng in sup.engines:
                if eng._step_times:
                    eng.record_measurements()
            log.save(args.record)
            print(f"recorded {len(log)} measurement(s) -> {args.record}")
        return
    if art is not None:
        eng = ServeEngine.from_artifact(
            art, max_batch=min(8, args.requests),
            max_seq=args.prompt_len + args.max_new,
            scheduler=args.scheduler, measurements=log, mesh=mesh)
        print(f"serving artifact {args.artifact} "
              f"(model={cfg.name}, target={art.target.name}, "
              f"oracle={art.oracle.name}, tuned_digest={art.tuned_digest})")
    else:
        params = init_params(jax.random.PRNGKey(0), cfg)
        if mesh is not None:
            from repro.serve.distributed import ShardedServeEngine
            eng = ShardedServeEngine(
                cfg, params, mesh=mesh, max_batch=min(8, args.requests),
                max_seq=args.prompt_len + args.max_new,
                scheduler=args.scheduler, measurements=log)
        else:
            eng = ServeEngine(cfg, params, max_batch=min(8, args.requests),
                              max_seq=args.prompt_len + args.max_new,
                              scheduler=args.scheduler, measurements=log)
    for req in synthetic_requests(args, cfg, budgets):
        eng.submit(req)
    stats = eng.run()
    _print_stats(stats)
    if log is not None:
        log.save(args.record)
        print(f"recorded {len(log)} measurement(s) -> {args.record}")


if __name__ == "__main__":
    main()
