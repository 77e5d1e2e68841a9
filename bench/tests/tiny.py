"""Cells cut down to CPU test size, with the program's kernels on `ref`
(plain jnp) unless a test asks for the Pallas bodies in interpret mode.

The sizes keep every part of a run (data, set-up build, warm-up, window,
check) and change only how much of each there is."""
from __future__ import annotations

import time

from bench import harness

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


def spec(name: str, n: int | None = None, root=harness.ROOT) -> harness.Spec:
    """The cell at CPU size.  Build cells take 8,000 points: at fewer,
    a beam of 128 finds the neighbours even on a graph whose build lost
    half its vertices' updates."""
    s = harness.resolve(name, root)
    build = s.traffic["mode"] == "build"
    s.config.update(n=n or (8000 if build else 3000), n_queries=256)
    s.config["data"]["points_per_cluster"] = 400
    s.config["build"].update(chunk_size=1024, t1=2, t2=2)
    tr = s.traffic
    if tr["mode"] == "build":
        tr.update(eval_queries=128, graph_sample=512, corpora=2)
    elif tr["mode"] == "open_loop":
        tr.update(rate_qps=60, max_batch=8)
    elif tr["mode"] == "closed_loop":
        tr.update(batch=64, batches=2)
    return s


def run(s: harness.Spec, seed: int = 2**33 + 7, seconds: float = 1.0):
    return harness.execute(s, seed, seconds, False, time.perf_counter(), CPU)


def interpret_spec(name: str) -> harness.Spec:
    """Smaller still, for the Pallas kernel bodies in interpret mode,
    which step their grids from Python."""
    s = spec(name, n=512)
    s.config["build"].update(s=8, r=16, pairs_per_vertex=16, chunk_size=256,
                             t1=2, t2=1)
    s.config["data"]["points_per_cluster"] = 100
    s.config["n_queries"] = 64
    tr = s.traffic
    tr["ef"] = 64
    if tr["mode"] == "build":
        tr.update(eval_queries=32, graph_sample=64, corpora=1)
    elif tr["mode"] == "open_loop":
        tr.update(rate_qps=20, max_batch=4)
    elif tr["mode"] == "closed_loop":
        tr.update(batch=16, batches=1)
    return s
