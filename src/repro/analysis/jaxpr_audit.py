"""Jaxpr auditor: trace the serve/train steps abstractly and walk them.

Everything here runs on :class:`jax.ShapeDtypeStruct` avals — params
come from ``jax.eval_shape(init_params, ...)``, caches/pools from
``eval_shape`` over their init functions — so a 100B-parameter config
audits in milliseconds without materializing a single buffer, and the
pass works identically on CPU and TPU hosts.

``J001 f32-promotion``
    a projection/FFN-shaped ``dot_general`` (fewer than two batch dims)
    whose *inputs* are f32 inside a bf16-configured step. Attention's
    online-softmax contractions (two batch dims) intentionally run in
    f32 and are exempt; so is anything fed bf16 with an f32 accumulator
    (``preferred_element_type`` promotion is the MXU regime, not a bug).
``J002 host-transfer``
    ``device_put`` / callback primitives inside the step: each one is a
    host<->device round trip per decode token.
``J003 missed-donation``
    the paged pools argument is not donated into the engine's jitted
    step — without ``tf.aliasing_output`` on the pool buffers every
    decode token copies the whole pool (:func:`audit_engine_donation`
    inspects the *engine's actual* jitted callables).
``J004 recompile-hazard``
    serve shapes outside the pow2/bucket sets the scheduler guarantees:
    ``compact="exact"`` retraces per width, a non-pow2 ``max_batch``
    adds a stray width, a ``max_seq`` off the page grid strays off the
    pow2-padded table column set.
``J005 replicated-param``
    a large parameter that resolves to fully-replicated under a sharded
    ``(data, model)`` mesh spec — every model shard holds a full copy,
    so tensor parallelism buys no HBM for it. Advisory: small tables
    (norm scales, router gates) are *meant* to replicate; the check only
    names leaves above a size floor.

The sharding-related checks are device-free: J005 uses
:class:`repro.sharding.rules.SpecMesh` (spec math reads only the mesh
*shape*), and :func:`audit_engine_donation` / :func:`audit_engine_steps`
audit a live engine's own jits, which is the same abstract tracing
whether the engine is single-device or a mesh-sharded
:class:`~repro.serve.distributed.ShardedServeEngine` — so J002/J003 run
under a tp=2 mesh exactly as under one device.

Severities: shipped configs must audit error-free, so J001/J004/J005 are
warnings (observations about numerics/layout/compile behavior) and
J002/J003 — which are outright serving bugs — are errors.
"""
from __future__ import annotations

import re
from typing import Any, Dict, List, Optional, Set, Tuple

import jax
import numpy as np

from repro.analysis.diagnostics import ERROR, WARNING, Diagnostic
from repro.configs.base import ModelConfig
from repro.models.model import Model, init_params
from repro.models.paged_cache import init_paged_pools, paged_compatible

try:  # jax >= 0.4.33 exposes the stable jaxpr types under jax.extend
    from jax.extend import core as jex_core
    _JAXPR_TYPES = (jex_core.Jaxpr, jex_core.ClosedJaxpr)
except (ImportError, AttributeError):  # pragma: no cover - older jax
    from jax import core as jex_core
    _JAXPR_TYPES = (jex_core.Jaxpr, jex_core.ClosedJaxpr)

#: primitives that force a host<->device round trip inside a step
_TRANSFER_PRIMS = {"device_put", "pure_callback", "io_callback",
                   "outside_call", "infeed", "outfeed"}
_DEBUG_PRIMS = {"debug_callback", "debug_print"}


def _as_jaxpr(x):
    return x.jaxpr if hasattr(x, "jaxpr") else x


def _iter_eqns(jaxpr):
    """Depth-first over every equation, including sub-jaxprs (scan/cond/
    while/pjit bodies)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            if isinstance(v, _JAXPR_TYPES):
                yield from _iter_eqns(_as_jaxpr(v))
            elif isinstance(v, (tuple, list)):
                for x in v:
                    if isinstance(x, _JAXPR_TYPES):
                        yield from _iter_eqns(_as_jaxpr(x))


def audit_jaxpr(jaxpr, *, site: str, expect_bf16: bool) -> List[Diagnostic]:
    """J001/J002 over one traced step."""
    jaxpr = _as_jaxpr(jaxpr)
    out: List[Diagnostic] = []
    seen_dots: Set[Tuple] = set()
    for eqn in _iter_eqns(jaxpr):
        name = eqn.primitive.name
        if name in _TRANSFER_PRIMS:
            out.append(Diagnostic(
                "J002", ERROR, f"{site}:{name}",
                f"{name} inside the jitted step forces a host<->device "
                f"transfer every invocation",
                fix_hint="move the transfer outside the step (feed the "
                         "value as an argument)"))
        elif name in _DEBUG_PRIMS:
            out.append(Diagnostic(
                "J002", WARNING, f"{site}:{name}",
                f"{name} inside the jitted step synchronizes with the "
                f"host",
                fix_hint="strip debug callbacks from production steps"))
        elif name == "dot_general" and expect_bf16:
            lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
            if (lhs.dtype == np.float32 and rhs.dtype == np.float32):
                (_, _), (lb, _rb) = eqn.params["dimension_numbers"]
                if len(lb) < 2:
                    key = (tuple(lhs.shape), tuple(rhs.shape), tuple(lb))
                    if key in seen_dots:
                        continue
                    seen_dots.add(key)
                    out.append(Diagnostic(
                        "J001", WARNING,
                        f"{site}:dot_general{list(lhs.shape)}x"
                        f"{list(rhs.shape)}",
                        "f32 x f32 GEMM inside a bf16-configured step "
                        "(4x MXU cost vs bf16 in / f32 accum)",
                        fix_hint="keep operands bf16 and request the "
                                 "f32 accumulator via "
                                 "preferred_element_type"))
    return out


# -- abstract tracing helpers ----------------------------------------------

def _sds(shape, dtype) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct(tuple(shape), np.dtype(dtype))


def _abstract(tree):
    return jax.tree.map(lambda a: _sds(a.shape, a.dtype), tree)


def param_avals(cfg: ModelConfig):
    """The param pytree as ShapeDtypeStructs — no materialization."""
    return jax.eval_shape(lambda k: init_params(k, cfg),
                          _sds((2,), np.uint32))


def trace_decode_step(cfg: ModelConfig, *, max_batch: int = 8,
                      max_seq: int = 512):
    model = Model(cfg)
    params = param_avals(cfg)
    caches = jax.eval_shape(lambda: model.init_caches(max_batch, max_seq))
    token = _sds((max_batch, 1), np.int32)
    return jax.make_jaxpr(model.decode_step)(params, token, caches)


def trace_decode_step_paged(cfg: ModelConfig, *, max_batch: int = 8,
                            max_seq: int = 512, page_size: int = 16):
    model = Model(cfg)
    params = param_avals(cfg)
    n_blocks = 2 + max_batch * (-(-max_seq // page_size))
    pools = jax.eval_shape(
        lambda: init_paged_pools(model, n_blocks, page_size))
    n_cols = -(-max_seq // page_size)
    token = _sds((max_batch, 1), np.int32)
    table = _sds((max_batch, n_cols), np.int32)
    pos = _sds((), np.int32)
    return jax.make_jaxpr(model.decode_step_paged)(params, token, pools,
                                                   table, pos)


def trace_prefill_chunk(cfg: ModelConfig, *, max_batch: int = 8,
                        max_seq: int = 512, page_size: int = 16,
                        chunk: int = 32):
    model = Model(cfg)
    params = param_avals(cfg)
    n_blocks = 2 + max_batch * (-(-max_seq // page_size))
    pools = jax.eval_shape(
        lambda: init_paged_pools(model, n_blocks, page_size))
    n_cols = -(-max_seq // page_size)
    tokens = _sds((max_batch, chunk), np.int32)
    table = _sds((max_batch, n_cols), np.int32)
    start = _sds((), np.int32)
    last = _sds((), np.int32)
    return jax.make_jaxpr(model.prefill_chunk_paged)(
        params, tokens, pools, table, start, last)


def _batch_avals(cfg: ModelConfig, batch: int, seq: int):
    """One train batch as avals, shaped per frontend (mirrors
    ``launch.specs.batch_specs``)."""
    if cfg.frontend == "audio_frames":
        return {"frames": _sds((batch, seq, cfg.d_model), cfg.dtype),
                "labels": _sds((batch, seq), np.int32),
                "mask": _sds((batch, seq), np.bool_)}
    b = {"tokens": _sds((batch, seq), np.int32)}
    if cfg.frontend == "vision_patches":
        f = min(cfg.frontend_seq, seq // 2)
        b["patch_embeds"] = _sds((batch, f, cfg.d_model), cfg.dtype)
    return b


def trace_train_step(cfg: ModelConfig, *, batch: int = 2, seq: int = 64):
    model = Model(cfg)
    params = param_avals(cfg)

    def step(p, b):
        loss, _metrics = model.loss_fn(p, b)
        return loss
    return jax.make_jaxpr(jax.grad(step))(params,
                                          _batch_avals(cfg, batch, seq))


# -- the pass ---------------------------------------------------------------

def audit_model(cfg: ModelConfig, *, max_batch: int = 8, max_seq: int = 512,
                page_size: int = 16, include_train: bool = True
                ) -> List[Diagnostic]:
    """J001/J002 over the decode step, the paged decode/chunked-prefill
    steps (paged-compatible configs), and the train step."""
    bf16 = cfg.dtype == "bfloat16"
    out = audit_jaxpr(
        trace_decode_step(cfg, max_batch=max_batch, max_seq=max_seq),
        site=f"{cfg.name}/decode_step", expect_bf16=bf16)
    if paged_compatible(cfg):
        out.extend(audit_jaxpr(
            trace_decode_step_paged(cfg, max_batch=max_batch,
                                    max_seq=max_seq, page_size=page_size),
            site=f"{cfg.name}/decode_step_paged", expect_bf16=bf16))
        if cfg.rope != "mrope" and cfg.frontend == "none":
            out.extend(audit_jaxpr(
                trace_prefill_chunk(cfg, max_batch=max_batch,
                                    max_seq=max_seq, page_size=page_size,
                                    chunk=2 * page_size),
                site=f"{cfg.name}/prefill_chunk_paged", expect_bf16=bf16))
    if include_train:
        out.extend(audit_jaxpr(
            trace_train_step(cfg),
            site=f"{cfg.name}/train_step", expect_bf16=bf16))
    return out


def audit_serve_shapes(scheduler_config, *, max_batch: int = 8,
                       max_seq: int = 512) -> List[Diagnostic]:
    """J004: static recompilation hazards in a serve configuration."""
    out: List[Diagnostic] = []
    sc = scheduler_config
    if sc.compact == "exact":
        out.append(Diagnostic(
            "J004", WARNING, "scheduler.compact",
            "compact='exact' retraces the decode step once per distinct "
            "surviving width (O(max_batch) compiles)",
            fix_hint="use compact='pow2' (O(log max_batch) shapes)"))
    if max_batch & (max_batch - 1):
        out.append(Diagnostic(
            "J004", WARNING, "max_batch",
            f"max_batch={max_batch} is not a power of two; admitted "
            f"full-width groups add a stray decode shape outside the "
            f"pow2 compaction set",
            fix_hint="size max_batch to a power of two"))
    if sc.kv_layout == "paged" and max_seq % sc.page_size:
        out.append(Diagnostic(
            "J004", WARNING, "max_seq",
            f"max_seq={max_seq} is not a multiple of "
            f"page_size={sc.page_size}; the last block is permanently "
            f"part-padded and table growth strays off the pow2 column "
            f"grid",
            fix_hint="round max_seq to a page_size multiple"))
    return out


def audit_param_sharding(cfg: ModelConfig, *, tp: int = 2,
                         min_mib: float = 1.0) -> List[Diagnostic]:
    """J005: params left fully replicated by the sharding rules under a
    ``(1, tp)`` mesh spec. Device-free — the rule table is resolved
    against a :class:`~repro.sharding.rules.SpecMesh`, so a 100B config
    audits on a 1-CPU host."""
    from repro.sharding import rules
    if tp < 2:
        return []
    mesh = rules.SpecMesh({"data": 1, "model": int(tp)})
    avals = param_avals(cfg)
    pspecs = rules.param_pspecs(avals, mesh)
    floor = int(min_mib * (1 << 20))
    out: List[Diagnostic] = []

    def model_sharded(spec) -> bool:
        # the data axis is size 1 on a serving mesh, so only a 'model'
        # entry means the param is actually split across shards
        for ax in tuple(spec):
            axes = ax if isinstance(ax, (tuple, list)) else (ax,)
            if "model" in axes:
                return True
        return False

    def walk(avals, specs, prefix=""):
        for k in sorted(avals):
            path = f"{prefix}/{k}" if prefix else k
            a, s = avals[k], specs[k]
            if isinstance(a, dict):
                walk(a, s, path)
                continue
            nbytes = int(np.prod(a.shape)) * np.dtype(a.dtype).itemsize
            if nbytes < floor or model_sharded(s):
                continue
            out.append(Diagnostic(
                "J005", WARNING, f"{cfg.name}/{path}",
                f"param {path} ({nbytes / (1 << 20):.1f} MiB, shape "
                f"{list(a.shape)}) is not sharded over the model axis "
                f"under a (1, {tp}) (data, model) mesh — each of the "
                f"{tp} model shards holds a full copy",
                fix_hint="add a trailing-dim rule for it in "
                         "repro.sharding.rules (or accept replication "
                         "for small/irregular tables)"))

    walk(avals, pspecs)
    return out


def audit_engine_steps(engine) -> List[Diagnostic]:
    """J001/J002 over a live engine's *actual* jitted decode step.
    Tracing is abstract and placement-blind, so this runs identically
    for a single-device engine and a tp>1
    :class:`~repro.serve.distributed.ShardedServeEngine` — the mesh
    changes where buffers live, not what the jaxpr contains."""
    bf16 = engine.cfg.dtype == "bfloat16"
    site = f"{engine.cfg.name}@tp{getattr(engine, 'tp', 1)}"
    params = _abstract(engine.params)
    cur = _sds((engine.max_batch, 1), np.int32)
    if getattr(engine, "kv_layout", "contiguous") == "paged":
        sc = engine.scheduler.config
        n_cols = max(1, -(-engine.max_seq // sc.page_size))
        pools = _abstract(engine._pools)
        table = _sds((engine.max_batch, n_cols), np.int32)
        pos = _sds((), np.int32)
        jaxpr = jax.make_jaxpr(engine.model.decode_step_paged)(
            params, cur, pools, table, pos)
        return audit_jaxpr(jaxpr, site=f"{site}/decode_step_paged",
                           expect_bf16=bf16)
    caches = jax.eval_shape(
        lambda: engine.model.init_caches(engine.max_batch, engine.max_seq))
    jaxpr = jax.make_jaxpr(engine.model.decode_step)(params, cur, caches)
    return audit_jaxpr(jaxpr, site=f"{site}/decode_step", expect_bf16=bf16)


def audit_engine_donation(engine) -> List[Diagnostic]:
    """J003 against a live engine's *actual* jitted paged steps: lower
    them at the engine's shapes and require pool aliasing in the
    lowered text. Contiguous engines trivially pass."""
    out: List[Diagnostic] = []
    if getattr(engine, "kv_layout", "contiguous") != "paged":
        return out
    sc = engine.scheduler.config
    n_cols = max(1, -(-engine.max_seq // sc.page_size))
    params = _abstract(engine.params)
    pools = _abstract(engine._pools)
    cur = _sds((engine.max_batch, 1), np.int32)
    table = _sds((engine.max_batch, n_cols), np.int32)
    pos = _sds((), np.int32)
    temps = _sds((engine.max_batch,), np.float32)
    key = _abstract(engine.key)
    checks = [("decode_step_paged",
               lambda: engine._decode_paged.lower(params, cur, pools,
                                                  table, pos, temps, key))]
    if sc.prefill_chunk:
        toks = _sds((engine.max_batch, sc.prefill_chunk), np.int32)
        checks.append(("prefill_chunk_paged",
                       lambda: engine._chunk_step.lower(
                           params, toks, pools, table, pos, pos)))
    # both steps return the pools right after their first output, so the
    # pool leaves are flat outputs 1..n; other donated arguments (the
    # decode step's PRNG key) must not stand in for them
    pool_outputs = set(range(1, 1 + len(jax.tree.leaves(pools))))
    for name, lower in checks:
        text = lower().as_text()
        aliased = {int(i) for i in
                   re.findall(r"tf\.aliasing_output = (\d+)", text)}
        if not pool_outputs <= aliased:
            out.append(Diagnostic(
                "J003", ERROR, f"engine.{name}",
                "the block pools are not donated into the jitted step — "
                "every invocation copies the entire KV pool",
                fix_hint="jit with donate_argnums=<pools arg index>"))
    return out
