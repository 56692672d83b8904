"""What every runner of the benchmark shares: where the checkout is,
the compile cache, the device record, compile statistics, percentiles
and the list of numbers that decide ``correct``.

Nothing here imports JAX at module import: the parent of a fed job must
stay off the chip.
"""

import json
import math
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE_BYTES = 2 * 1024 ** 3  # room for every cell's programs


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def fix_compile_cache():
    """One fixed cache directory for this process and every child it
    starts: where ``JAX_COMPILATION_CACHE_DIR`` points, else
    ``<checkout>/.jax_cache`` (the path is part of the cache key, so it
    never moves). Exported so that the program's own
    ``util.enable_compile_cache`` takes the same one."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    # The cells' programs and their references take some hundreds of MB
    # together. Under a smaller cap the cache evicts them in turn, and
    # every run of the serving cell compiled 8-12 programs again (PERF.md,
    # PR 26): the cap is raised to hold them all, never lowered.
    cap = os.environ.get("JAX_COMPILATION_CACHE_MAX_SIZE", "")
    if cap.lstrip("-").isdigit() and 0 <= int(cap) < CACHE_BYTES:
        os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = str(CACHE_BYTES)
    for floor in ("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS",
                  "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"):
        os.environ.setdefault(floor, "0")


def work_dir(name):
    """A scratch directory inside the checkout (git-ignored)."""
    path = os.path.join(ROOT, ".bench_work", name)
    os.makedirs(path, exist_ok=True)
    return path


def make_ctx(name, cell, config, traffic, seed, seconds, trace=False,
             platform="tpu", t0_epoch=None):
    """What a runner's ``run(ctx)`` takes, with a fresh scratch directory
    ``.bench_work/<name>`` for whatever the run leaves on disk."""
    import shutil
    import time

    work = work_dir(name)
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    return {"cell": cell, "config": config, "traffic": traffic,
            "seed": seed, "seconds": seconds, "trace": bool(trace),
            "platform": platform, "work_dir": work,
            "trace_dir": os.path.join(work, "trace"),
            "t0_epoch": time.time() if t0_epoch is None else t0_epoch}


class NoChip(RuntimeError):
    """JAX found no accelerator, too few chips, or a device that the
    table of peaks does not know."""


def peaks_for(kind):
    table = load_json("peaks.json")["devices"]
    if kind not in table:
        raise NoChip("device kind {!r} is not in benchmarks/peaks.json "
                     "(known: {})".format(kind, sorted(table)))
    return table[kind]


def device_record(platform, chips):
    """The device as JAX reports it. Raises :class:`NoChip` unless JAX
    runs on ``platform`` with at least ``chips`` devices and (on a TPU)
    the device kind has peaks."""
    import jax

    dev = jax.devices()
    if dev[0].platform != platform:
        raise NoChip("wanted a {!r} device, JAX found {}".format(
            platform, dev))
    if len(dev) < chips:
        raise NoChip("the cell needs {} chip(s), JAX found {}".format(
            chips, len(dev)))
    if platform == "tpu":
        peaks_for(dev[0].device_kind)
    return {"platform": dev[0].platform, "kind": dev[0].device_kind,
            "count": len(dev)}


def memory_stats(device=None):
    """One device's ``memory_stats()`` as plain integers ({} on the CPU)."""
    import jax

    device = device or jax.devices()[0]
    return {k: int(v) for k, v in (device.memory_stats() or {}).items()}


def memory_peak_bytes(n_devices=None):
    """Peak bytes held on the fullest device, as JAX's ``memory_stats``
    reports them: the allocator's ``peak_bytes_in_use`` (arrays) plus
    ``peak_bytes_reserved`` - the temporaries of loaded programs, which
    the TPU runtime reserves "at the bottom of memory" and does not count
    as in use (shown on the chip, PERF.md PR 26: a 2 GB reservation
    fails with RESOURCE_EXHAUSTED once arrays leave less than that). 0
    where the backend reports none, as the CPU does."""
    import jax

    peak = 0
    for d in jax.devices()[:n_devices]:
        stats = memory_stats(d)
        peak = max(peak, stats.get("peak_bytes_in_use", 0)
                   + stats.get("peak_bytes_reserved", 0))
    return peak


def host_load():
    """What the host's cores were doing, to tell a run that reads far
    off because the machine was busy from one that is slow by itself:
    CPU seconds of all cores together by kind (``/proc/stat``; ``steal``
    is what the hypervisor gave to other guests), this process's CPU
    seconds and its involuntary context switches. Take one before and
    one after a window and subtract (:func:`host_load_between`)."""
    import resource
    import time

    out = {}
    try:
        with open("/proc/stat") as f:
            fields = f.readline().split()[1:]
        tick = float(os.sysconf("SC_CLK_TCK"))
        names = ("user", "nice", "system", "idle", "iowait", "irq",
                 "softirq", "steal")
        out = {"cpu_{}_s".format(n): int(v) / tick
               for n, v in zip(names, fields)}
    except (OSError, ValueError):
        pass  # no /proc: the process's own readings stand alone
    ru = resource.getrusage(resource.RUSAGE_SELF)
    out["process_cpu_s"] = ru.ru_utime + ru.ru_stime
    out["thread_cpu_s"] = time.thread_time()  # the calling thread's own
    out["involuntary_switches"] = ru.ru_nivcsw
    return out


def host_load_between(before, after):
    return {k: after[k] - before[k] for k in after if k in before}


class CompileStats(object):
    """Programs this process compiled (or fetched from the persistent
    cache) and the seconds that took, from JAX's monitoring events.
    (Copied from ``chip_smoke.py``; the original is listed in PERF.md
    for a later PR to delete.)"""

    def __init__(self):
        import jax

        self.programs = self.cache_hits = self.cache_misses = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1
            self.seconds += seconds

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def snapshot(self):
        return {"programs": self.programs, "compile_seconds": self.seconds,
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses}


def percentile(values, q):
    """Nearest-rank percentile (q in 0..100) of a non-empty list."""
    ordered = sorted(values)
    rank = max(1, int(math.ceil(q / 100.0 * len(ordered))))
    return ordered[rank - 1]


def median(values):
    return percentile(values, 50)


class Checks(object):
    """The numbers compared for ``correct``, each beside its limit. A
    number passes when it is at most its limit; a missing number (None,
    NaN) fails."""

    def __init__(self):
        self.rows = []

    def add(self, name, value, limit):
        ok = value is not None and value == value and value <= limit
        self.rows.append({"name": name, "value": value, "limit": limit,
                          "ok": bool(ok)})
        return ok

    @property
    def ok(self):
        return bool(self.rows) and all(r["ok"] for r in self.rows)

    def as_dict(self):
        return {r["name"]: [r["value"], r["limit"]] for r in self.rows}


class TraceWindow(object):
    """A profiler trace of the first ``seconds`` of a run, stopped by a
    helper thread so that whoever drives the load is not held up while
    the trace is written out. ``done`` is set once the trace has been
    written: counters that a per-layer metric reads are taken from then
    on, clear of the profiler's own cost. The traced stretch is the
    host span ``bench:window``, which the reduction takes as the window.
    """

    def __init__(self, trace_dir, seconds):
        import threading

        self.trace_dir, self.seconds = trace_dir, seconds
        self.done = threading.Event()
        self.t0 = self.t1 = None
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="bench-trace-window")

    def start(self):
        import time

        import jax

        # the Python tracer hooks every Python call: it slowed the feed's
        # consumer threefold in the traced stretch (PERF.md, PR 26), so
        # it is off; TraceAnnotation spans and the runtime's own remain
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        self.t0 = time.monotonic()
        self._thread.start()

    def _run(self):
        import time

        import jax

        try:
            with jax.profiler.TraceAnnotation("bench:window"):
                time.sleep(self.seconds)
            self.t1 = time.monotonic()
            jax.profiler.stop_trace()
        finally:
            self.done.set()

    def join(self):
        self._thread.join()
