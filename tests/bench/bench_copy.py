"""A copy of the benchmark at a size a test run can hold, and a way to run
one of its cells on the CPU."""
from __future__ import annotations

import contextlib
import io
import json
import pathlib
import shutil
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def edit(path: pathlib.Path, fn) -> None:
    d = json.loads(path.read_text())
    fn(d)
    path.write_text(json.dumps(d))


def small_copy(dst: pathlib.Path) -> pathlib.Path:
    """BENCHMARK.json and bench/ under ``dst``, every cell cut to a few
    seconds of CPU work; the pins follow the cut."""
    from bench.generators import cluster

    dst.mkdir(parents=True, exist_ok=True)
    shutil.copy(ROOT / "BENCHMARK.json", dst)
    shutil.copytree(ROOT / "bench", dst / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    b = dst / "bench"

    def acad(d):
        d["fleet"].update(n_devices=16, horizon_s=1200)
        d["pin"].update(rows=16 * 1200, sha256=cluster.digest(d))

    for name in ("acad64_3h", "acad192_3h"):
        edit(b / "configs" / f"{name}.json", acad)
    edit(b / "traffic" / "search.json",
         lambda d: d.update(max_evals=40, max_rounds=2))
    return dst


def run_cell(root, *argv, require_tpu=False, patch=None):
    """``bench/run.py`` in this process; returns (exit code, the last line
    of standard output parsed, standard error)."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    import repro.obs as obs
    from bench import run

    out, err = io.StringIO(), io.StringIO()
    keep = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    try:
        with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
            rc = run.main(list(argv), root=pathlib.Path(root),
                          require_tpu=require_tpu)
    finally:
        for k, v in keep.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
        obs.disable()
        obs.reset()
    lines = out.getvalue().strip().splitlines()
    return rc, (json.loads(lines[-1]) if lines else None), err.getvalue()
