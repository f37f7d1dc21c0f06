"""A cell small enough for the CPU: a copy of ``bench/`` in a temporary
checkout, with a two-layer model and a light mix added as files (and, for
``tiny_two_blocks``, an architecture: ``two_blocks.py`` beside this file
goes to the copy's ``bench/reference/``)."""
from __future__ import annotations

import json
import pathlib
import shutil

BENCH = pathlib.Path(__file__).resolve().parents[1]

MODEL = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
         "head_dim": 16, "d_ff": 128, "vocab_size": 256, "qk_norm": True,
         "rope_theta": 1000000.0, "tie_embeddings": True,
         "dtype": "bfloat16"}
MOE_MODEL = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
             "head_dim": 16, "d_ff": 32, "vocab_size": 256, "n_experts": 8,
             "top_k": 2, "moe_d_ff": 32, "qk_norm": False,
             "rope_theta": 10000.0, "tie_embeddings": True,
             "dtype": "bfloat16"}

CONFIGS = {
    "tiny_dense": {"repro_config": "qwen3_1_7b", "model": MODEL},
    "tiny_moe": {"repro_config": "granite_moe_1b_a400m", "model": MOE_MODEL,
                 "extra": {"moe_cf": 4.0}},
    # three layers: block positions pos0 and pos1, and one tail layer
    "tiny_two_blocks": {"repro_config": "qwen3_1_7b",
                        "model": dict(MODEL, n_layers=3),
                        "extra": {"block_pattern": ["attn", "local_attn"]},
                        "reference": "two_blocks"},
}

MIXES = {
    "tinychat": {"loop": "open", "rate_per_s": 40.0, "lead_in_s": 0.2,
                 "schedule_seed": 1,
                 "prompt_len": {"values": [16, 32], "weights": [0.5, 0.5]},
                 "output_len": {"lognormal": {"median": 4, "sigma": 0.5},
                                "min": 2, "max": 8}},
    "tinydoc": {"loop": "closed", "clients": 3, "lead_in_s": 0.2,
                "schedule_seed": 1,
                "prompt_len": {"values": [32, 48], "weights": [1, 1]},
                "output_len": {"values": [2, 4], "weights": [1, 1]}},
}


def checkout(tmp: pathlib.Path, config: str = "tiny_dense",
             mix: str = "tinychat", limit: float = 0.5,
             dtype: str = "bfloat16", max_batch: int = 2,
             reading: str = "max_logit_gap") -> pathlib.Path:
    """A checkout under ``tmp`` holding BENCHMARK.json with one cell
    ``<config>.<mix>``, reporting every end-to-end metric of the real
    BENCHMARK.json, and the real bench/ plus the tiny files."""
    root = tmp / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    c = CONFIGS[config]
    reference = c.get("reference", "decoder")
    if reference != "decoder":
        shutil.copy(BENCH / "tests" / f"{reference}.py",
                    root / "bench" / "reference" / f"{reference}.py")
    model = dict(c["model"], dtype=dtype)
    overrides = dict(model, **c.get("extra", {}))
    cfg = {"source": "test", "repro_config": c["repro_config"],
           "overrides": overrides, "reference": reference,
           "model": model,
           "serve": {"max_batch": max_batch, "page_size": 16},
           "limits": {reading: limit}}
    (root / "bench" / "configs" / f"{config}.json").write_text(
        json.dumps(cfg))
    (root / "bench" / "traffic" / f"{mix}.json").write_text(
        json.dumps(MIXES[mix]))
    cell = f"{config}.{mix}"
    e2e = [m["name"] for m in
           json.loads((BENCH.parent / "BENCHMARK.json").read_text())[
               "end_to_end"]]
    per_layer = (["ttft_p90_s", "decode_calls_per_tick", "tick_ms.chat"]
                 if MIXES[mix]["loop"] == "open"
                 else ["prefill_ms_per_ktok.longdoc", "step_mfu.longdoc"])
    real = {"workloads": [{"name": cell, "config": config, "traffic": mix,
                           "chips": 1, "why": "test"}],
            "end_to_end": [{"name": n, "unit": "u"} for n in e2e],
            "per_layer": [{"name": n, "unit": "u"} for n in per_layer]}
    (root / "BENCHMARK.json").write_text(json.dumps(real))
    return root


CPU_PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11,
             "hbm_bytes": 1e10}
