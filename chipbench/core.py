"""Shared plumbing of the chip benchmark: the manifest and the files it
names, the device check, compile counting, and the result line.

Everything a cell needs is found by name: ``BENCHMARK.json`` names the
workload, its configuration (``configs/<config>.json``) and its traffic
(``traffic/<traffic>.json``); each per-layer metric is read by
``metrics/<name>.py``. Adding a cell, a configuration, a traffic mix or
a metric adds files and edits none.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import math
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent


class BenchError(RuntimeError):
    """A run that cannot produce a result (wrong device, bad manifest)."""


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def manifest(root: pathlib.Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def workload(man: dict, name: str) -> dict:
    for w in man["workloads"]:
        if w["name"] == name:
            return w
    raise BenchError(f"no workload {name!r} in BENCHMARK.json")


def config_entry(man: dict, name: str) -> dict:
    for c in man["configs"]:
        if c["name"] == name:
            return c
    raise BenchError(f"no configuration {name!r} in BENCHMARK.json")


def load_cell(man: dict, name: str, root: pathlib.Path = ROOT,
              traffic_dir: pathlib.Path | None = None) -> dict:
    """The workload entry with its configuration and traffic files."""
    w = workload(man, name)
    c = config_entry(man, w["config"])
    tdir = pathlib.Path(traffic_dir) if traffic_dir else HERE / "traffic"
    return {"workload": w, "config": load_json(root / c["file"]),
            "traffic": load_json(tdir / f"{w['traffic']}.json")}


def cell_metrics(man: dict, name: str, group: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics this cell reports: a
    metric with a ``workloads`` list applies to those cells only."""
    return [m for m in man[group]
            if "workloads" not in m or name in m["workloads"]]


def load_module(kind: str, name: str):
    """Import ``<kind>/<name>.py`` (names may hold dots)."""
    path = HERE / kind / f"{name}.py"
    if not path.exists():
        raise BenchError(f"no {kind} file for {name!r}: {path}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name.replace('.', '_').replace('-', '_')}",
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_runner(cell: dict):
    """The module that runs a traffic file of ``"kind": "<kind>"``:
    ``chipbench/<kind>_cell.py``."""
    kind = cell["traffic"]["kind"]
    if not (HERE / f"{kind}_cell.py").exists():
        raise BenchError(f"no runner chipbench/{kind}_cell.py for the "
                         f"traffic kind {kind!r}")
    return importlib.import_module(f"chipbench.{kind}_cell")


def peaks(device_kind: str) -> dict:
    table = load_json(HERE / "peaks.json")["devices"]
    if device_kind not in table:
        raise BenchError(f"device kind {device_kind!r} is not in "
                         "chipbench/peaks.json; add its published peaks")
    return table[device_kind]


def check_devices(jax, chips: int, require_tpu: bool = True) -> dict:
    """Fail unless JAX sees at least ``chips`` TPU devices whose kind has
    published peaks. Returns the device record of the result line."""
    devs = jax.devices()
    plat = devs[0].platform
    kind = devs[0].device_kind
    if require_tpu and plat != "tpu":
        raise BenchError(f"no TPU: JAX's first device is {plat!r}")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX sees "
                         f"{len(devs)}")
    if require_tpu:
        peaks(kind)
    return {"platform": plat, "kind": kind, "count": chips}


def memory_peak_bytes(jax, chips: int) -> int:
    """Peak device memory of the fullest chip: its buffers at their peak
    plus the scratch the runtime reserved for the compiled programs'
    temporaries, which ``peak_bytes_in_use`` leaves out."""
    peak = 0
    for d in jax.devices()[:chips]:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0))
                   + int(st.get("peak_bytes_reserved", 0)))
    return peak


class CompileCounter:
    """Counts lowerings of new jitted programs (each needs an executable,
    from the persistent cache or from the compiler)."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self, jax):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, _secs, **_kw):
        if name == self.EVENT:
            self.n += 1


def setup_jax(jax) -> None:
    """The program's fixed compile cache; every program goes into it, so
    a cell's second run finds them all."""
    from repro.launch.compile_cache import setup_compile_cache
    setup_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile (numpy's default) without numpy."""
    xs = sorted(values)
    if not xs:
        return float("nan")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def emit(result: dict, checks: list[dict]) -> None:
    """Print the compared numbers beside their limits on stderr (last
    lines) and the result as the last line of stdout, checks last."""
    for c in checks:
        print(f"check {c['name']}: {c['value']!r} limit {c['limit']!r} "
              f"({'ok' if c['ok'] else 'FAILED'})", file=sys.stderr)
    result = dict(result)
    result["checks"] = {c["name"]: {"value": c["value"],
                                    "limit": c["limit"]}
                        for c in checks}
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return result


def out_dir() -> pathlib.Path:
    """Scratch space inside the checkout (listed in .gitignore)."""
    d = ROOT / ".chipbench"
    d.mkdir(exist_ok=True)
    return d


class Clock:
    """Monotonic host clock, zeroed at process start."""

    def __init__(self, t0: float | None = None):
        self.t0 = time.monotonic() if t0 is None else t0

    def __call__(self) -> float:
        return time.monotonic() - self.t0
