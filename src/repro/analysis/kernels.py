"""Static checker for the Pallas kernel launches (no compilation).

Every kernel in :mod:`repro.kernels` launches from a small amount of
host-side geometry — block shapes, padded operand dims, a grid, VMEM
scratch. This pass re-derives that geometry (mirroring each kernel's own
padding/clipping math) as a :class:`KernelCall` and validates it against
the active/passed :class:`~repro.api.targets.TargetSpec`:

``K001 tile-not-divisible``
    a block breaks the TPU lowering's tiling rule: each of its last two
    dims must be a multiple of the hardware extent it maps onto
    (second-minor -> SUBLANE, minor -> LANE) or equal the operand's own
    dim there. This is the rule Mosaic enforces when it compiles a
    ``BlockSpec``, so a K001-clean launch is not refused for its blocks.
``K002 grid-bounds``
    a grid dimension <= 0, or a total step count past int32.
``K003 vmem-overflow``
    the per-call footprint (double-buffered input blocks + f32
    accumulator/scratch, :func:`cost_model.block_vmem_bytes` for GEMMs
    and the same convention for the rest) exceeds ``target.vmem_bytes``.
``K004 dtype-rule``
    inputs wider than f32 (unsupported on the MXU) — error; f32 inputs
    on a GEMM kernel (bf16 in / f32 accum is the expected regime) —
    warning.

:func:`check_model_kernels` enumerates the whole launch set one config
implies — every tuned GEMM in its task table plus the attention/scan
kernels its layer kinds use at the serve shapes — which is what the CLI
and the artifact export stamp run. Everything here is plain arithmetic:
no jit, no kernel build, no device.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

from repro.analysis.diagnostics import ERROR, WARNING, Diagnostic
from repro.configs.base import ATTN, LOCAL_ATTN, RGLRU, RWKV
from repro.core import cost_model, oracle as oracle_mod, tuner, tuning_cache
from repro.core.cost_model import Block
from repro.core.tasks import TaskTable, Workload, local_gemm_dims
from repro.models.paged_cache import RESERVED_BLOCKS

_MAX_GRID_STEPS = 2**31 - 1


def _ceil_to(x: int, b: int) -> int:
    return -(-x // b) * b


@dataclasses.dataclass(frozen=True)
class KernelCall:
    """One Pallas launch, statically described.

    ``blocks`` maps an operand name to ``(block_shape, array_shape)`` —
    each ``BlockSpec`` of the launch over the (padded) array it tiles, the
    K001 inputs; ``vmem_bytes`` is the double-buffered footprint.
    """

    kernel: str                    # matmul | moe_gmm | flash_attention | ...
    site: str                      # human label ("stack/pos0:ffn up" etc.)
    grid: Tuple[int, ...]
    blocks: Dict[str, Tuple[Tuple[int, ...], Tuple[int, ...]]]
    vmem_bytes: int
    dtype_bytes: int
    is_gemm: bool = True


# -- per-kernel describers (mirror each kernel's launch math) ---------------

def describe_matmul(m: int, k: int, n: int, block: Block, *,
                    dtype_bytes: int = 2, site: str = "matmul"
                    ) -> KernelCall:
    bm, bk, bn = block.bm, block.bk, block.bn
    mp, kp, np_ = _ceil_to(m, bm), _ceil_to(k, bk), _ceil_to(n, bn)
    return KernelCall(
        kernel="matmul", site=site,
        grid=(mp // bm, np_ // bn, kp // bk),
        blocks={"a": ((bm, bk), (mp, kp)), "b": ((bk, bn), (kp, np_)),
                "out": ((bm, bn), (mp, np_))},
        vmem_bytes=cost_model.block_vmem_bytes(bm, bk, bn, dtype_bytes),
        dtype_bytes=dtype_bytes)


def describe_moe_gmm(n_experts: int, c: int, k: int, n: int, block: Block, *,
                     dtype_bytes: int = 2, site: str = "moe_gmm"
                     ) -> KernelCall:
    # the kernel clips the block to the operand dims before padding
    bm, bk, bn = min(block.bm, c), min(block.bk, k), min(block.bn, n)
    cp, kp, np_ = _ceil_to(c, bm), _ceil_to(k, bk), _ceil_to(n, bn)
    e = n_experts
    return KernelCall(
        kernel="moe_gmm", site=site,
        grid=(e, cp // bm, np_ // bn, kp // bk),
        blocks={"x": ((1, bm, bk), (e, cp, kp)),
                "w": ((1, bk, bn), (e, kp, np_)),
                "out": ((1, bm, bn), (e, cp, np_))},
        vmem_bytes=cost_model.block_vmem_bytes(bm, bk, bn, dtype_bytes),
        dtype_bytes=dtype_bytes)


def describe_flash_attention(batch: int, sq: int, sk: int, n_heads: int,
                             head_dim: int, *, bq: int = 128, bk: int = 128,
                             dtype_bytes: int = 2,
                             site: str = "flash_attention") -> KernelCall:
    bq = min(bq, max(sq, 8))
    bk = min(bk, max(sk, 8))
    sqp, skp = _ceil_to(max(sq, 8), bq), _ceil_to(max(sk, 8), bk)
    d, bh = head_dim, batch * n_heads
    # q/k/v blocks double-buffered + f32 online-softmax scratch
    # ((bq,128) running max + (bq,128) running sum + (bq,D) accumulator)
    vmem = (2 * dtype_bytes * (bq * d + 2 * bk * d)
            + 4 * (2 * bq * 128 + bq * d))
    return KernelCall(
        kernel="flash_attention", site=site,
        grid=(bh, sqp // bq, skp // bk),
        blocks={"q": ((1, bq, d), (bh, sqp, d)),
                "kv": ((1, bk, d), (bh, skp, d)),
                "out": ((1, bq, d), (bh, sqp, d))},
        vmem_bytes=vmem, dtype_bytes=dtype_bytes, is_gemm=False)


def describe_paged_attention(batch: int, n_heads: int, head_dim: int,
                             n_cols: int, page_size: int, *,
                             n_kv_heads: int, dtype_bytes: int = 2,
                             site: str = "paged_attention") -> KernelCall:
    d, bs, hq, hkv = head_dim, page_size, n_heads, n_kv_heads
    n_blocks = RESERVED_BLOCKS + batch * n_cols
    # q (1,Hq,D) + one KV block (1,bs,Hkv,D) each way, double-buffered;
    # f32 scratch (Hq,128)x2 + (Hq,D)
    vmem = (2 * dtype_bytes * (hq * d + 2 * bs * hkv * d)
            + 4 * (2 * hq * 128 + hq * d))
    return KernelCall(
        kernel="paged_attention", site=site,
        grid=(batch, n_cols),
        blocks={"q": ((1, hq, d), (batch, hq, d)),
                "kv": ((1, bs, hkv, d), (n_blocks, bs, hkv, d)),
                "out": ((1, hq, d), (batch, hq, d))},
        vmem_bytes=vmem, dtype_bytes=dtype_bytes, is_gemm=False)


def describe_rwkv6_scan(batch: int, seq: int, n_heads: int, head_dim: int, *,
                        bs: int = 64, dtype_bytes: int = 2,
                        site: str = "rwkv6_scan") -> KernelCall:
    bs = min(bs, seq)
    sp = _ceil_to(seq, bs)
    d, bh = head_dim, batch * n_heads
    # r/k/v/w blocks + the (D,) bonus row, double-buffered; f32 state
    # scratch (D,D) + carried state block (D,D)
    vmem = (2 * dtype_bytes * (4 * bs * d + d) + 4 * 2 * d * d)
    return KernelCall(
        kernel="rwkv6_scan", site=site,
        grid=(bh, sp // bs),
        blocks={"rkvw": ((1, bs, d), (bh, sp, d)),
                "u": ((1, 1, d), (n_heads, 1, d)),
                "out": ((1, bs, d), (bh, sp, d)),
                "state": ((1, d, d), (bh, d, d))},
        vmem_bytes=vmem, dtype_bytes=dtype_bytes, is_gemm=False)


def describe_rglru_scan(batch: int, seq: int, width: int, *, bs: int = 128,
                        bw: int = 128, dtype_bytes: int = 2,
                        site: str = "rglru_scan") -> KernelCall:
    bs, bw = min(bs, seq), min(bw, width)
    sp, wp = _ceil_to(seq, bs), _ceil_to(width, bw)
    # a/x blocks double-buffered + f32 carry scratch (1,bw)
    vmem = 2 * dtype_bytes * (2 * bs * bw) + 4 * bw
    return KernelCall(
        kernel="rglru_scan", site=site,
        grid=(batch, wp // bw, sp // bs),
        blocks={"ax": ((1, bs, bw), (batch, sp, wp)),
                "out": ((1, bs, bw), (batch, sp, wp))},
        vmem_bytes=vmem, dtype_bytes=dtype_bytes, is_gemm=False)


# -- checks -----------------------------------------------------------------

def check_call(call: KernelCall, target) -> List[Diagnostic]:
    """Validate one described launch against ``target`` (anything with
    ``vmem_bytes``; ``lane``/``sublane`` default to the cost model's)."""
    out: List[Diagnostic] = []
    where = f"{call.kernel}[{call.site}]"
    lane, sublane = _target_geom(target)
    for name, (block, array) in call.blocks.items():
        for axis, hw in ((-2, sublane), (-1, lane)):
            b, a = block[axis], array[axis]
            if b % hw and b != a:
                out.append(Diagnostic(
                    "K001", ERROR, where,
                    f"{name} block {block} over array {array}: dim "
                    f"{axis} is {b}, neither a multiple of {hw} nor the "
                    f"array's {a}",
                    fix_hint=f"make that block dim a multiple of {hw} or "
                             f"the whole array dim"))
    if any(g <= 0 for g in call.grid):
        out.append(Diagnostic(
            "K002", ERROR, where,
            f"grid {call.grid} has a non-positive dimension",
            fix_hint="operand dims and blocks must be >= 1"))
    else:
        steps = 1
        for g in call.grid:
            steps *= g
        if steps > _MAX_GRID_STEPS:
            out.append(Diagnostic(
                "K002", ERROR, where,
                f"grid {call.grid} totals {steps} steps (> int32)",
                fix_hint="grow the blocks; the grid must index in int32"))
    vmem_budget = int(getattr(target, "vmem_bytes"))
    if call.vmem_bytes > vmem_budget:
        out.append(Diagnostic(
            "K003", ERROR, where,
            f"per-call VMEM footprint {call.vmem_bytes} B exceeds the "
            f"target budget {vmem_budget} B",
            fix_hint="shrink the block config (or retune for this "
                     "target — the tuner filters candidates by VMEM)"))
    if call.dtype_bytes > 4:
        out.append(Diagnostic(
            "K004", ERROR, where,
            f"{call.dtype_bytes}-byte inputs are unsupported on the MXU",
            fix_hint="cast inputs to bf16 (or f32)"))
    elif call.dtype_bytes == 4 and call.is_gemm:
        out.append(Diagnostic(
            "K004", WARNING, where,
            "f32 GEMM inputs; the MXU regime is bf16 in / f32 accum",
            fix_hint="store weights/activations in bf16 and keep the "
                     "f32 accumulator"))
    return out


def _target_geom(target) -> Tuple[int, int]:
    return (int(getattr(target, "lane", cost_model.LANE)),
            int(getattr(target, "sublane", cost_model.SUBLANE)))


def check_table_kernels(table: TaskTable, target) -> List[Diagnostic]:
    """K-checks for every tuned GEMM program in a task table."""
    out: List[Diagnostic] = []
    for task in table.tasks:
        site = task.sites[0]
        for gname, prog in task.programs.items():
            label = f"{site.site_id} {gname}"
            if site.kind in ("moe_ffn",) and prog.batch > 1:
                call = describe_moe_gmm(
                    prog.batch, prog.m, prog.k, prog.n, prog.block,
                    dtype_bytes=prog.dtype_bytes, site=label)
            else:
                call = describe_matmul(
                    prog.m, prog.k, prog.n, prog.block,
                    dtype_bytes=prog.dtype_bytes, site=label)
            out.extend(check_call(call, target))
    return out


def check_model_kernels(cfg, target, *, table: Optional[TaskTable] = None,
                        workload: Optional[Workload] = None,
                        max_batch: int = 8, max_seq: int = 512,
                        page_size: int = 16,
                        sites: Optional[Sequence] = None
                        ) -> List[Diagnostic]:
    """The full launch set one config implies on ``target``.

    GEMMs come from ``table`` (an artifact's embedded
    :class:`TaskTable`); when none is given, a table is tuned here under
    a *private* ProgramCache with the target activated only for the
    duration — a check run never touches the process-wide caches
    (see :func:`tests.test_analysis`'s no-global-mutation test).
    Attention/scan launches are derived from the config's layer kinds at
    the serve shapes.
    """
    from repro.models.model import prune_sites
    db = 2 if cfg.dtype == "bfloat16" else 4
    out: List[Diagnostic] = []

    if table is None:
        site_list = list(sites) if sites is not None else prune_sites(cfg)
        wl = workload or Workload(tokens_global=max_batch * max_seq)
        cache = tuning_cache.ProgramCache()   # private: no global fallout
        with tuner.target_activation(target), \
                oracle_mod.use_oracle("analytic"):
            table = TaskTable(site_list, wl)
            for task in table.tasks:
                s = task.sites[0]
                epi = tuner._epilogue_ops_for(s.op_kind)
                for g in s.gemms:
                    m, k, n, b = local_gemm_dims(s, g, wl)
                    task.programs[g.name] = tuner.tune_gemm(
                        m, k, n, batch=b, dtype_bytes=wl.dtype_bytes,
                        epilogue_ops=epi, cache=cache)
                task.tuned_mode = "tuned"
    out.extend(check_table_kernels(table, target))

    kinds = set(cfg.layer_kinds())
    if kinds & {ATTN, LOCAL_ATTN}:
        out.extend(check_call(describe_flash_attention(
            max_batch, max_seq, max_seq, cfg.n_heads, cfg.head_dim,
            dtype_bytes=db, site=f"{cfg.name} prefill"), target))
        n_cols = -(-max_seq // page_size)
        out.extend(check_call(describe_paged_attention(
            max_batch, cfg.n_heads, cfg.head_dim, n_cols, page_size,
            n_kv_heads=cfg.n_kv_heads, dtype_bytes=db,
            site=f"{cfg.name} paged decode"), target))
    if RWKV in kinds:
        out.extend(check_call(describe_rwkv6_scan(
            max_batch, max_seq, max(1, cfg.d_model // cfg.rwkv_head_dim),
            cfg.rwkv_head_dim, dtype_bytes=db,
            site=f"{cfg.name} rwkv6"), target))
    if RGLRU in kinds:
        out.extend(check_call(describe_rglru_scan(
            max_batch, max_seq, cfg.rglru_width, dtype_bytes=db,
            site=f"{cfg.name} rglru"), target))
    return out


def check_artifact_kernels(artifact) -> List[Diagnostic]:
    """K-checks for a :class:`DeploymentArtifact` against its *own*
    target, using its embedded tuned table (no retuning, no global
    state). This is what the export stamp records."""
    defaults = artifact.metadata.get("serve_defaults") or {}
    return check_model_kernels(
        artifact.cfg, artifact.target, table=artifact.table,
        workload=artifact.workload,
        max_batch=defaults.get("max_batch", 8),
        max_seq=defaults.get("max_seq", 512))


def pool_blocks_for(max_batch: int, max_seq: int, page_size: int) -> int:
    """The engine's default pool sizing (kept here for CLI reporting)."""
    return RESERVED_BLOCKS + max_batch * (-(-max_seq // page_size))
