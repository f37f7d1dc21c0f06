"""Artifact smoke: export a deployment artifact in THIS process, then
serve it from a SECOND ``python -c`` interpreter (fresh process, no
shared tuning caches), and assert the two processes agree on the tuned
fingerprint while the serve stats are non-empty.

This is the CI ``artifact-smoke`` job — the export -> load -> serve
separation the artifact layer exists for: the expensive prune/tune
session lives and dies in process one; process two restarts the serve
path from disk alone. It is a CPU correctness check: the child runs with
``JAX_PLATFORMS=cpu`` whatever the parent's backend, so a parent that
holds a chip never shares it with its child.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile

from benchmarks import common
from repro.api import CPruneConfig, PruningSession, TrainHooks, Workload
from repro.core import clear_tuning_caches

# runs in a second interpreter: cold caches, no PruningSession, artifact
# directory as argv[1]; prints one JSON line the parent asserts on
_CHILD = """
import json, sys
import numpy as np
from repro.api.artifact import DeploymentArtifact
from repro.serve.engine import Request, ServeEngine

art = DeploymentArtifact.load(sys.argv[1])
eng = ServeEngine.from_artifact(art, max_batch=2, max_seq=24)
rng = np.random.default_rng(0)
for i in range(2):
    eng.submit(Request(rid=i,
                       prompt=rng.integers(0, art.cfg.vocab_size,
                                           8).astype(np.int32),
                       max_new_tokens=4))
stats = eng.run()
print(json.dumps({"tuned_digest": art.tuned_digest,
                  "requests": stats["requests"],
                  "total_new_tokens": stats["total_new_tokens"],
                  "p95_ttft_s": stats["p95_ttft_s"],
                  "p95_step_s": stats["p95_step_s"],
                  "predicted_step_s": stats["predicted_step_s"],
                  "outputs": [r.output for r in eng.done]}))
"""


def _child_env() -> dict:
    import repro
    # repro is a namespace package (__file__ is None): locate src via
    # __path__ so the child resolves the same tree regardless of cwd
    src = os.path.dirname(os.path.abspath(list(repro.__path__)[0]))
    env = dict(os.environ)
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    return env


def run():
    t = common.Timer()
    clear_tuning_caches()
    cfg = common.bench_config(n_layers=2, d_model=64, d_ff=512, n_heads=4,
                              n_kv_heads=2, head_dim=16, vocab_size=128)
    session = PruningSession(
        cfg, workload=Workload(tokens_global=2048),
        hooks=TrainHooks(short_term_train=lambda p, s: p,
                         eval_acc=lambda p, s: 1.0),
        pcfg=CPruneConfig(a_g=0.0, seq_len=64, max_iterations=2))
    session.prune(strategy="uniform_l1", ratio=0.5)

    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "artifact")
        art = session.export(path, max_batch=2, max_seq=24)
        proc = subprocess.run([sys.executable, "-c", _CHILD, path],
                              capture_output=True, text=True,
                              env=_child_env(), timeout=480)
        if proc.returncode != 0:
            raise RuntimeError(f"artifact serve subprocess failed:\n"
                               f"{proc.stderr[-2000:]}")
        blob = json.loads(proc.stdout.strip().splitlines()[-1])

    fingerprints_match = blob["tuned_digest"] == art.tuned_digest
    stats_nonempty = (blob["requests"] == 2
                      and blob["total_new_tokens"] == 8
                      and blob["p95_ttft_s"] > 0.0
                      and blob["p95_step_s"] > 0.0
                      and all(blob["outputs"]))
    derived = (f"fingerprints_match={fingerprints_match}"
               f";stats_nonempty={stats_nonempty}"
               f";requests={blob['requests']}"
               f";tokens={blob['total_new_tokens']}"
               f";p95_ttft_s={blob['p95_ttft_s']:.3f}"
               f";predicted_step_s={blob['predicted_step_s']}")
    common.emit("artifact_smoke", t.us(), derived)
    clear_tuning_caches()
    if not (fingerprints_match and stats_nonempty):
        # RuntimeError (not SystemExit) so benchmarks/run.py's harness can
        # record the failure row and keep running the remaining figures
        raise RuntimeError(f"artifact smoke failed: {derived}")
    return blob


if __name__ == "__main__":
    run()
