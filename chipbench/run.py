"""Run one benchmark cell once, on the chip, and print its result.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

from the repository root. The cell, its configuration, its traffic mix
and its metrics are looked up by name (``spec.py``). The run

1. sets up (``setup_s``, from process start): records from the seed
   installed into the store's last level, a fixed warm-up of Puts
   through ``StorageService.submit``, a drain, Bloom filters and the
   cell's Get shapes where the cell reads, every count of queries the
   memory component can leave to the store probe among them;
2. measures a closed loop for ``--seconds``: one submit in flight, each
   a Get or a Put batch drawn from the mix, timed on the host clock
   around ``submit`` (results come back as numpy, so the device work is
   done when it returns). With ``--trace 1`` the profiler records the
   window and the per-layer metrics are read from it instead;
3. reads back acknowledged updates through the program (all of the last
   Put and a sample of the others drawn from the seed), frees the
   program, and replays the plain reference (``reference.py``) over the
   whole log: every Get answer of the window and of the read-back is
   compared, found flag and value.

It fails (non-zero exit, no result line) off a TPU, with fewer chips
than the cell asks for, or where the Pallas backend would interpret its
kernels. The last line of standard output is one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics`` and ``device`` (and
``breakdown`` in a traced run), and ``checks`` last: each number
compared with its limit, which also end standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()    # set-up is timed from here

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from chipbench import probes, reference, spec, store, trace  # noqa: E402
from chipbench.peaks import peaks  # noqa: E402
from chipbench.traffic import TrafficMix  # noqa: E402

CACHE_DIR = ROOT / ".jax_cache"
TAIL_MIN_SUBMITS = 200           # ten beyond the 95th percentile
READBACK_BATCHES = 64            # the read-back's sample, in Get batches


class Phases:
    """Logs the seconds each set-up phase took."""

    def __init__(self, t0: float):
        self.t = t0

    def __call__(self, what: str) -> None:
        now = time.perf_counter()
        log(f"set-up: {what} in {now - self.t:.3f} s")
        self.t = now


class NoChip(RuntimeError):
    """The run cannot be measured on this machine."""


def log(msg: str) -> None:
    print(f"chipbench: {msg}", file=sys.stderr, flush=True)


def check_device(chips: int) -> dict:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found platform {devs[0].platform!r} "
                     f"({devs[0].device_kind}), not a TPU")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def use_compile_cache() -> None:
    """The persistent compilation cache at a fixed directory inside the
    checkout, for every compile however short."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_enable_compilation_cache", True)


@dataclass
class Window:
    """What the closed loop did, on the host clock."""

    log: list = field(default_factory=list)          # reference.Logged
    latency: dict = field(default_factory=lambda: {"get": [], "put": []})
    keys_done: int = 0
    failed: int = 0
    seconds: float = 0.0


def run_window(svc, submits, names, seconds: float, annotate) -> Window:
    from repro.core import Get, Put, WriteAck
    w = Window()
    t0 = time.perf_counter()
    deadline = t0 + seconds
    with annotate("window"):
        while time.perf_counter() < deadline:
            s = next(submits)
            with annotate("submit"):
                a = time.perf_counter()
                if s.kind == "get":
                    (res,) = svc.submit([Get(names[s.tree], s.keys)])
                else:
                    (res,) = svc.submit_all(
                        [Put(names[s.tree], s.keys, s.vals)])
                b = time.perf_counter()
            w.latency[s.kind].append(b - a)
            if s.kind == "get":
                w.log.append(reference.Logged(
                    "get", s.tree, s.keys, found=res.found, got=res.vals))
                w.keys_done += len(s.keys)
                continue
            acked = isinstance(res, WriteAck)
            w.log.append(reference.Logged("put", s.tree, s.keys, s.vals,
                                          acked=acked))
            if acked:
                w.keys_done += len(s.keys)
            else:
                w.failed += 1
    w.seconds = time.perf_counter() - t0
    return w


def read_back(svc, names, keys_per_tree, batch: int) -> list:
    """Get every key in ``keys_per_tree`` through the program, in batches
    of the cell's size (the last padded with its own last key, so no new
    shape compiles). Returns per tree (found, vals)."""
    from repro.core import Get
    out = []
    for name, keys in zip(names, keys_per_tree):
        found = np.zeros(len(keys), bool)
        vals = np.zeros(len(keys), np.int64)
        for a in range(0, len(keys), batch):
            k = keys[a:a + batch]
            m = len(k)
            if m < batch:
                k = np.concatenate([k, np.repeat(k[-1:], batch - m)])
            (res,) = svc.submit([Get(name, k)])
            found[a:a + m] = res.found[:m]
            vals[a:a + m] = res.vals[:m]
        out.append((found, vals))
    return out


def percentile_ms(samples, q: float) -> float:
    return float(np.percentile(np.asarray(samples), q) * 1e3)


@dataclass
class Context:
    """What a per-layer metric reader (``metrics/<name>.py``) reads."""

    trace: trace.Trace | None     # None in a run that traced nothing
    counts: dict                   # counter deltas over the window
    compiles: int
    calls: dict                    # probes.Spans.calls
    peaks: dict


def end_to_end(cell: spec.Cell, w: Window, setup_s: float) -> dict:
    values = {"setup_s": setup_s, "ops_per_s": w.keys_done / w.seconds}
    for kind in ("get", "put"):
        lat = w.latency[kind]
        name = "read_p95_ms" if kind == "get" else "write_p95_ms"
        if lat:
            values[name] = percentile_ms(lat, 95)
            log(f"{kind} submits: {len(lat)}, p50 "
                f"{percentile_ms(lat, 50):.3f} ms, p95 {values[name]:.3f} ms")
        if any(m["name"] == name for m in cell.end_to_end) \
                and len(lat) < TAIL_MIN_SUBMITS:
            log(f"warning: {name} from {len(lat)} submits, fewer than "
                f"{TAIL_MIN_SUBMITS}")
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end if m["name"] in values}


def per_layer(cell: spec.Cell, ctx: Context) -> dict:
    out = {}
    for m in cell.per_layer:
        v = spec.metric_reader(m["name"])(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


@dataclass
class Outcome:
    """A run's result object without its verdict, and what the verdict
    is judged from: the records, the warm-up's Puts, the window and the
    read-back."""

    result: dict
    keys: list
    vals: list
    warm: list
    window: Window
    back_keys: list
    back: list


def execute(cell: spec.Cell, seed: int, seconds: float, traced: bool, *,
            require_tpu: bool = True, t_start: float = T_START) -> Outcome:
    """Set up, measure and read back one run of ``cell`` (see the module
    docstring). ``require_tpu=False`` skips the look for a chip, for the
    rehearsals in ``tests/``."""
    import jax
    if require_tpu:
        device = check_device(cell.chips)
    else:
        d = jax.devices()[0]
        device = {"platform": d.platform, "kind": d.device_kind, "count": 1}
    use_compile_cache()
    config = cell.config
    names = store.tree_names(config)
    rng_data, rng_warm, rng_reads, rng_window, rng_back = [
        np.random.default_rng(s)
        for s in np.random.SeedSequence(seed).spawn(5)]

    svc = store.open_service(config)
    be = svc.store.backend
    if be.name != "pallas":
        raise NoChip(f"the store's backend resolved to {be.name!r}")
    if require_tpu and be.interpret:
        raise NoChip("the Pallas backend would interpret its kernels")
    phase = Phases(t_start)
    keys, vals = store.record_data(config, rng_data)
    for name, k, v in zip(names, keys, vals):
        store.install_last_level(svc.store, name, k, v)
    phase("records installed")
    mix = TrafficMix(cell.mix, len(names))
    warm = store.warm_up(svc, mix.submits(rng_warm, keys, puts_only=True),
                         int(config["warmup_updates"]), names)
    phase(f"{len(warm)} warm-up Put batches and a drain")
    if "put" in mix.kinds:
        n = store.warm_merges(be, config, mix.batch)
        phase(f"{n} merges, one per pair of run sizes")
    if "get" in mix.kinds:
        n = store.build_blooms(svc.store)
        phase(f"{n} Bloom filters")
        n = store.warm_reads(svc, mix.submits(rng_reads, keys), names)
        phase(f"{n} warm Get batches")
        n = store.warm_searches(be, config, mix.batch)
        phase(f"{n} memory-component searches, one per pair of sizes")
        n = store.warm_unresolved(svc, mix.submits(rng_reads, keys),
                                  names, keys, mix.batch, rng_reads)
        phase(f"{n} Get batches, one per count the store probe can see")
    setup_s = time.perf_counter() - t_start
    log(f"set-up: {setup_s:.3f} s, "
        f"{len(store.disk_tables(svc.store))} disk tables")

    before = probes.counters(svc)
    spans = probes.Spans() if traced else None
    captured: dict = {}
    annotate = (lambda name: jax.profiler.TraceAnnotation(
        "chipbench." + name)) if traced \
        else (lambda name: contextlib.nullcontext())
    with probes.CompileCounter() as compiles, \
            (spans or contextlib.nullcontext()), \
            (trace.capture(captured) if traced
             else contextlib.nullcontext()):
        compiles.active = True
        w = run_window(svc, mix.submits(rng_window, keys), names, seconds,
                       annotate)
        compiles.active = False
    counts = probes.delta(probes.counters(svc), before)
    stats = jax.devices()[0].memory_stats() or {}
    device["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))
    log(f"window: {w.seconds:.3f} s, {len(w.log)} submits "
        f"({len(w.latency['put'])} Puts), {compiles.count} compiles "
        f"({compiles.seconds:.3f} s): {dict(compiles.names.most_common(8))}")

    t = time.perf_counter()
    back_keys = reference.readback_keys(warm, w.log, len(names), rng_back,
                                        READBACK_BATCHES * mix.batch)
    back = read_back(svc, names, back_keys, mix.batch)
    log(f"read-back: {sum(len(k) for k in back_keys)} keys in "
        f"{time.perf_counter() - t:.3f} s")
    del svc
    gc.collect()

    result = {"attempted": len(w.log), "failed": w.failed}
    if traced:
        tr = captured["trace"]
        ctx = Context(trace=tr if tr.window else None, counts=counts,
                      compiles=compiles.count, calls=spans.calls,
                      peaks=peaks(device["kind"]) if require_tpu else {})
        result["metrics"] = per_layer(cell, ctx)
        if tr.window and tr.devices:
            device["busy_s"] = trace.busy_ns(tr) / 1e9
            device["window_s"] = (tr.window[1] - tr.window[0]) / 1e9
            result["breakdown"] = trace.breakdown(tr)
    else:
        result["metrics"] = end_to_end(cell, w, setup_s)
    result["device"] = device
    return Outcome(result, keys, vals, warm, w, back_keys, back)


def judge(o: Outcome, *, control: bool = False) -> dict:
    """The numbers compared, each with its limit: the window's Get
    answers and the read-back's that differ from the plain reference.
    With ``control`` the control's answers stand in the program's place
    (``reference.py``)."""
    rep = reference.replay(o.keys, o.vals, o.warm, o.window.log,
                           control=control)
    wrong_back = 0
    for t, (k, (found, got)) in enumerate(zip(o.back_keys, o.back)):
        if rep.control is not None:
            found, got = rep.control.get(t, k)
        wrong_back += reference.wrong_answers(found, got,
                                              *rep.reference.get(t, k))
    log(f"{rep.gets_compared} window Get answers and the read-back "
        f"compared" + (" (control)" if control else ""))
    return {"wrong_gets": {"value": rep.wrong_gets, "limit": 0},
            "wrong_readbacks": {"value": wrong_back, "limit": 0}}


def is_correct(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())


def run_cell(cell: spec.Cell, seed: int, seconds: float, traced: bool,
             **kw) -> dict:
    """One run of ``cell``: the result object, ``checks`` last."""
    o = execute(cell, seed, seconds, traced, **kw)
    t = time.perf_counter()
    checks = judge(o)
    log(f"reference replay in {time.perf_counter() - t:.3f} s")
    for name, c in checks.items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    return {"correct": is_correct(checks), **o.result, "checks": checks}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    try:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        log(f"cannot measure: {e}")
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
