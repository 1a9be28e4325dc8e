"""One run of one cell: set-up, a measured window, the output check.

Everything a cell is made of is found by name from ``BENCHMARK.json``:
its configuration in ``configs/<config>.json`` (whose ``design`` names
``designs/<design>.py``), its traffic mix in ``traffic/<traffic>.json``,
whose ``kind`` names the driver ``traffic/<kind>.py`` (the program's
entry, warm-up, window, output check and end-to-end metric of that kind
of traffic), the limits of its output check in ``limits/<workload>.json``
and each per-layer metric's reader in ``metrics/<metric>.py``.  A new
configuration, mix, kind or metric is a new file.  The program is
imported from the checkout's ``src``; the references, the traffic and
every number's arithmetic live here.
"""
from __future__ import annotations

import dataclasses
import hashlib
import importlib.util
import json
import os
import pathlib
import shutil
import sys
import tempfile
import time

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
FSTAR_DIR = BENCH / ".fstar"     # F* kept by (config, seed, reference code)


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def process_age_s() -> float:
    """Seconds since this process started (Linux ``/proc``)."""
    ticks = os.sysconf("SC_CLK_TCK")
    with open("/proc/self/stat") as f:
        start = int(f.read().rsplit(")", 1)[1].split()[19]) / ticks
    with open("/proc/uptime") as f:
        return float(f.read().split()[0]) - start


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# Cells, by name
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Cell:
    workload: dict
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list
    per_layer: list

    @property
    def name(self) -> str:
        return self.workload["name"]


def _json(path: pathlib.Path) -> dict:
    return json.loads(path.read_text())


def load_benchmark() -> dict:
    return _json(ROOT / "BENCHMARK.json")


def resolve(bm: dict, workload: str) -> Cell:
    """The cell named ``workload`` with its files and the metrics it
    reports.  A metric with a ``workloads`` list belongs to those cells; a
    per-layer metric without one belongs to every cell that reports the
    end-to-end metric it moves."""
    wl = {w["name"]: w for w in bm["workloads"]}
    if workload not in wl:
        raise KeyError(f"no workload {workload!r}; known: {sorted(wl)}")
    w = wl[workload]
    cfg = {c["name"]: c for c in bm["configs"]}[w["config"]]
    e2e = [m for m in bm["end_to_end"]
           if workload in m.get("workloads", [workload])]
    moved = {m["name"] for m in e2e}
    layer = [m for m in bm["per_layer"]
             if workload in m.get("workloads", [])
             or ("workloads" not in m and m["moves"] in moved)]
    return Cell(workload=w, config=_json(ROOT / cfg["file"]),
                traffic=_json(BENCH / "traffic" / f"{w['traffic']}.json"),
                limits=_json(BENCH / "limits" / f"{workload}.json"),
                end_to_end=e2e, per_layer=layer)


def _module(path: pathlib.Path, prefix: str):
    spec = importlib.util.spec_from_file_location(
        f"{prefix}_{path.stem.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric: str):
    """The ``read(ctx)`` function of ``metrics/<metric>.py``."""
    return _module(BENCH / "metrics" / f"{metric}.py", "bench_metric").read


def kind(name: str):
    """The driver module ``traffic/<name>.py`` of a traffic kind: its
    ``timed(cell)``, ``warm``, ``window``, ``check`` and ``end_to_end``."""
    return _module(BENCH / "traffic" / f"{name}.py", "bench_kind")


# ---------------------------------------------------------------------------
# Device and program
# ---------------------------------------------------------------------------

def devices(chips: int, require_chip: bool = True):
    import jax
    devs = jax.devices()
    if require_chip and devs[0].platform != "tpu":
        raise NoChip(f"JAX's platform is {devs[0].platform!r}, not 'tpu'")
    if require_chip and len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX has {len(devs)}")
    return devs[:chips]


def import_program():
    """Put the checkout's ``src`` and this directory on the path."""
    for p in (str(ROOT / "src"), str(BENCH)):
        if p not in sys.path:
            sys.path.insert(0, p)


def enable_cache() -> str:
    """Turn on the persistent compile cache where the program keeps it
    (``JAX_COMPILATION_CACHE_DIR`` if set, else ``.jax_cache`` in the
    checkout)."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    where = enable_compile_cache()
    # cache every program, however quick to compile, so that a second run
    # of a cell compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return where


class CompileCounter:
    """Counts backend compilations while ``on``: none may fall inside the
    measured window."""

    def __init__(self):
        import jax
        self.on, self.count = False, 0

        def listen(event, *_args, **_kw):
            if self.on and "backend_compile" in event:
                self.count += 1
        jax.monitoring.register_event_duration_secs_listener(listen)


# ---------------------------------------------------------------------------
# Problem and references
# ---------------------------------------------------------------------------

def build_problem(cfg: dict, key):
    """(A, y, lam): the configuration's design, made on the device, and
    the lambda it asks for."""
    import jax
    from reference import data
    if cfg["dtype"] != "float32":
        raise ValueError(f"dtype {cfg['dtype']!r}: only float32 is built")
    A, y = jax.block_until_ready(data.make_data(key, cfg))
    return A, y, float(cfg["lam"])


def _ref_hash(cfg: dict) -> str:
    h = hashlib.sha256(json.dumps(cfg, sort_keys=True).encode())
    for p in sorted([*(BENCH / "reference").glob("*.py"),
                     BENCH / "designs" / f"{cfg['design']}.py"]):
        h.update(p.read_bytes())
    return h.hexdigest()[:16]


def f_star(cfg: dict, seed: int, A, y, lam: float, keep: bool = True):
    """F* of the configuration at ``seed`` from the FISTA reference, kept
    in ``.fstar/`` by (configuration, seed, reference code)."""
    from reference import solvers
    path = FSTAR_DIR / f"{cfg['name']}-{seed}-{_ref_hash(cfg)}.json"
    if keep and path.exists():
        return _json(path)["f_star"]
    t = time.perf_counter()
    f, fs = solvers.f_star(A, y, lam, cfg["loss"], cfg["fista_iters"])
    f = float(f)
    q = [(float(fs[len(fs) * i // 4]) - f) / abs(f) for i in (1, 2)]
    log(f"F* from {len(fs)} FISTA iterations in "
        f"{time.perf_counter() - t:.3f} s: {f!r}; it moved {q[0]:.3e} "
        f"relative over the last 3/4 and {q[1]:.3e} over the last half")
    if keep:
        FSTAR_DIR.mkdir(exist_ok=True)
        path.write_text(json.dumps({"f_star": f}))
    return f


# ---------------------------------------------------------------------------
# What every traffic kind shares: the window loop and the check's arithmetic
# ---------------------------------------------------------------------------

def unit_key(traffic_key, i: int):
    import jax
    return jax.random.fold_in(traffic_key, i)


WARM = 2 ** 31 - 1     # fold-in index of the warm-up unit, never timed


def back_to_back(span: str, seconds: float, call) -> tuple[list, float]:
    """Runs ``call(i)`` for i = 0, 1, ... back to back, each inside the
    host span ``span``, until the window has lasted ``seconds``: the
    window holds whole units only.  Returns (their results, window s)."""
    import jax
    out = []
    t0 = time.perf_counter()
    while not out or time.perf_counter() - t0 < seconds:
        with jax.profiler.TraceAnnotation(span):
            out.append(call(len(out)))
    return out, time.perf_counter() - t0


def objectives(A, y, lam, X, loss):
    """(F of each row of X with a fresh A x, the margins A X^T)."""
    import jax.numpy as jnp
    import numpy as np
    from reference import solvers
    AX = solvers.dot(A, X.T)                          # (n, S)
    f = np.asarray([solvers.data_loss(AX[:, i], y, loss)
                    for i in range(X.shape[0])], np.float64)
    return f + lam * np.asarray(jnp.sum(jnp.abs(X), axis=1), np.float64), AX


def sample(n_units: int, k: int, seed: int) -> list[int]:
    """k of the window's units, drawn from the seed."""
    import numpy as np
    rng = np.random.default_rng(seed)
    return sorted(rng.choice(n_units, min(k, n_units), replace=False).tolist())


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """correct when every number with a limit is at or under it."""
    checks = {k: {"value": v, "limit": limits.get(k)}
              for k, v in numbers.items()}
    compared = [c for c in checks.values() if c["limit"] is not None]
    ok = all(c["value"] <= c["limit"] for c in compared)
    return ok and bool(compared), checks


def memory_peak(devs) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------

def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             require_chip: bool = True, timed=None, design=None,
             trace_dir=None, keep_fstar: bool = True) -> dict:
    """Set up, measure, check.  Returns the result line as a dict.
    ``timed`` replaces the program's timed path (for the fault tests);
    ``design`` replaces the design the timed path is handed by
    ``design(A)``, which may consume A (for the control): the check then
    reads the design made afresh from the seed."""
    import jax
    import_program()
    from reference import data, roofline, solvers
    phases = {"process_start": process_age_s()}
    t = time.perf_counter()
    devs = devices(cell.workload["chips"], require_chip)
    peak = roofline.peaks(devs[0].device_kind) if require_chip else None
    phases["jax_and_devices"] = time.perf_counter() - t

    t = time.perf_counter()
    cache_dir = enable_cache()
    counter = CompileCounter()
    phases["program_import"] = time.perf_counter() - t
    log(f"compile cache: {cache_dir}")

    cfg, traffic = cell.config, cell.traffic
    drv = kind(traffic["kind"])
    t = time.perf_counter()
    k_data, k_traffic = jax.random.split(data.seed_key(seed))
    A, y, lam = build_problem(cfg, k_data)
    if design is not None:
        A = jax.block_until_ready(design(A))
    phases["data"] = time.perf_counter() - t

    t = time.perf_counter()
    run = timed if timed is not None else drv.timed(cell)
    drv.warm(run, A, y, lam, k_traffic, traffic)
    phases["compile_or_cache_and_warm"] = time.perf_counter() - t
    setup_s = process_age_s()
    for name, s in phases.items():
        log(f"setup phase {name}: {s:.3f} s")
    log(f"setup_s: {setup_s:.3f}")

    tmp = None
    if trace:
        tmp = trace_dir or tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tmp, profiler_options=opts)
    counter.on = True
    with jax.profiler.TraceAnnotation("bench.window"):
        units, window_s = drv.window(run, A, y, lam, k_traffic, traffic,
                                     seconds)
    counter.on = False
    if trace:
        jax.profiler.stop_trace()
    log(f"window: {len(units)} units in {window_s:.3f} s, "
        f"{counter.count} compilations inside it")
    mem = memory_peak(devs)

    t = time.perf_counter()
    if design is not None:
        del run, A
        A, y, lam = build_problem(cfg, k_data)
    log(f"lambda {lam!r}, lambda_max "
        f"{float(solvers.lambda_max(A, y, cfg['loss']))!r}")
    fstar = f_star(cfg, seed, A, y, lam, keep=keep_fstar)
    numbers, counters = drv.check(cell, A, y, lam, fstar, units, k_traffic,
                                  seed)
    log(f"reference and check: {time.perf_counter() - t:.3f} s "
        f"(F* = {fstar!r})")

    e2e = drv.end_to_end(window_s, units, counters)
    e2e["setup_s"] = setup_s
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": mem}

    result = {"correct": None, "attempted": len(units),
              "failed": counters["failed"]}
    if not trace:
        result["metrics"] = {m["name"]: {"value": e2e[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
    else:
        from tracing import busy_ns, extract, idle_gaps, top_ops, window as win
        tr = extract(tmp)
        if trace_dir is None:
            shutil.rmtree(tmp, ignore_errors=True)
        w = win(tr)
        busy = busy_ns(tr, w)
        ctx = {"cell": cell.name, "config": cfg, "traffic": traffic,
               "counters": counters, "trace": tr, "window_ns": w,
               "busy_ns": busy, "peak": peak, "units": len(units)}
        result["metrics"] = {}
        for m in cell.per_layer:
            v = reader(m["name"])(ctx)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = busy * 1e-9
        device["window_s"] = (w[1] - w[0]) * 1e-9
        result["breakdown"] = {"device_ops": top_ops(tr, w),
                               "idle_gaps": idle_gaps(tr, w)}
    result["device"] = device
    correct, checks = judge(numbers, cell.limits)
    if counter.count:
        log(f"{counter.count} compilations fell inside the window")
    result["correct"] = correct
    result["checks"] = checks
    for k, c in checks.items():
        log(f"check {k}: {c['value']!r} (limit {c['limit']!r})")
    return result
