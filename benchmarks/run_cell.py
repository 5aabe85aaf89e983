#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmarks/run_cell.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, the only one that touches JAX. It finds the cell in
``BENCHMARK.json`` and everything that belongs to the cell in files beside
this one, each found by the name a data file gives it: the configuration
(``configs/``) and its model family (``families/``), the traffic mix
(``traffic/``) and its driver (``drivers/``), the per-layer metrics
(``layer_metrics/``) and their readers (``readers/``). Adding a
configuration, a family, a cell, a mix or a metric adds files and entries and
edits none (README.md).

Set-up (all of it counted in ``setup_s``): the configuration's reference
genomes, made once per checkout under ``benchmarks/.work/``; the callsets,
drawn from ``--seed``; the model, drawn from the configuration's
``weights_seed``; then one whole file per reference through the entry, so
that every genome is resident on the device and every program of the window
is compiled. The window is the traffic's driver's: for ``closed_loop_cli`` one
file after another through ``variantcalling_tpu.__main__.main``, the
references taking turns, until ``--seconds`` have passed, ending at the first
file boundary after that.

After the window it reads the device's peak memory, then compares a sample of
what the window's files wrote with the plain reference (``reference.py``).

Without a TPU (or with fewer chips than the cell asks for) it exits non-zero
and prints no result line. ``--rehearse <divisor>`` runs the same code with
genome and callset sizes divided, on whatever JAX finds, prints to stderr,
never prints a result line and exits 4.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import hashlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import resource  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
WORK = os.path.join(BENCH, ".work")
OUT_DIR = os.path.join(ROOT, "chiprun_out", "benchmarks")
EXIT_NO_TPU, EXIT_REHEARSAL, EXIT_NO_REPO, EXIT_NO_DISK = 3, 4, 5, 6
#: JAX monitoring events that mean "XLA compiled a program now"
MISS_EVENT = "/jax/compilation_cache/cache_misses"
HIT_EVENT = "/jax/compilation_cache/cache_hits"

sys.path.insert(0, BENCH)
sys.path.insert(0, ROOT)

import lookup  # noqa: E402


def say(msg: str) -> None:
    print(f"[run_cell {time.perf_counter() - T_START:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts), encoding="utf-8") as fh:
        return json.load(fh)


class Spec:
    """What ``BENCHMARK.json`` and the data files say about one cell."""

    def __init__(self, workload: str):
        bm = load_json(ROOT, "BENCHMARK.json")
        cells = {w["name"]: w for w in bm["workloads"]}
        if workload not in cells:
            raise SystemExit(f"no cell {workload!r} in BENCHMARK.json "
                             f"(cells: {sorted(cells)})")
        self.cell = cells[workload]
        self.run_seconds = bm["run_seconds"]
        entry = next(c for c in bm["configs"] if c["name"] == self.cell["config"])
        self.config = load_json(ROOT, entry["file"])
        self.traffic = load_json(BENCH, "traffic", self.cell["traffic"] + ".json")
        self.peaks = load_json(BENCH, "peaks.json")
        here = lambda m: "workloads" not in m or workload in m["workloads"]  # noqa: E731
        self.end_to_end = [m for m in bm["end_to_end"] if here(m)]
        self.per_layer = [m for m in bm["per_layer"] if here(m)]


def reader_fn(name: str):
    return lookup.load("readers", name).read


class CompileWatch:
    """Counts JAX's compilation-cache events (copied from chip_smoke.py): a
    miss is a program XLA compiled now, a hit one it loaded from disk."""

    def __init__(self) -> None:
        import jax

        self.counts: dict[str, int] = {}
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        if "compilation_cache" in event:
            self.counts[event] = self.counts.get(event, 0) + 1

    def take(self) -> dict[str, int]:
        out, self.counts = dict(sorted(self.counts.items())), {}
        return out


def body_count(data: bytes) -> int:
    """Records in a VCF text: lines that are not header lines."""
    n, pos = data.count(b"\n") + (0 if data.endswith(b"\n") or not data else 1), 0
    while data.startswith(b"#", pos):
        nl = data.find(b"\n", pos)
        n -= 1
        if nl < 0:
            break
        pos = nl + 1
    return n


def body_lines(data: bytes) -> list[bytes]:
    return [ln for ln in data.split(b"\n") if ln and not ln.startswith(b"#")]


# -- set-up -------------------------------------------------------------------

def ensure_references(config: dict, divisor: int) -> list[dict]:
    import fixtures

    refs = []
    for r in config["references"]:
        glen, nc = r["genome_len"] // divisor, r["n_contigs"]
        path = os.path.join(WORK, f"ref_{r['reference_seed']}_{glen}_{nc}.fa")
        if not os.path.exists(path):
            need = int(2.1 * glen) + (1 << 30)  # FASTA, the program's .venc sidecar, outputs
            free = shutil.disk_usage(WORK).free
            if free < need:
                say(f"refusing to write {path}: {free / 1e9:.1f} GB free under "
                    f"{WORK}, {need / 1e9:.1f} GB needed (FASTA and its encoded "
                    "sidecar)")
                raise SystemExit(EXIT_NO_DISK)
            t0 = time.perf_counter()
            fixtures.write_reference(path, r["reference_seed"], glen, nc)
            say(f"wrote reference {os.path.basename(path)} in "
                f"{time.perf_counter() - t0:.1f}s")
        refs.append({"path": path, "seed": r["reference_seed"], "genome_len": glen,
                     "n_contigs": nc})
    return refs


# -- one run ------------------------------------------------------------------

class Run:
    say = staticmethod(say)

    def __init__(self, spec: Spec, seed: int, seconds: float, trace: bool,
                 divisor: int = 1, control: str | None = None):
        self.spec, self.seed, self.seconds, self.trace = spec, seed, seconds, trace
        self.divisor, self.control = divisor, control
        self.config, self.traffic = spec.config, spec.traffic
        self.family = lookup.load("families", self.config["family"])
        self.driver = lookup.load("drivers", self.traffic["driver"])
        self.n = self.config["variants_per_file"] // divisor
        self.dir = os.path.join(WORK, "run")
        self.trace_dir = os.path.join(WORK, "trace")
        self.files: list[dict] = []
        self.traced: dict = {}

    def set_up(self) -> None:
        os.makedirs(WORK, exist_ok=True)
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir)
        self.refs = ensure_references(self.config, self.divisor)
        self.new_seed(self.seed)
        self.new_weights(self.config["weights_seed"])
        self.compiles = CompileWatch()
        self.driver.warm_up(self)

    def new_seed(self, seed: int) -> None:
        import fixtures

        self.seed = seed
        t0 = time.perf_counter()
        for ref in self.refs:
            ref["calls"] = os.path.join(self.dir, f"calls_{ref['seed']}.vcf")
            fixtures.write_callset(ref["calls"], ref["seed"], ref["genome_len"],
                                   ref["n_contigs"], self.n, seed)
        say(f"wrote {len(self.refs)} callsets of {self.n} variants (seed {seed}) in "
            f"{time.perf_counter() - t0:.1f}s")

    def new_weights(self, weights_seed: int) -> None:
        """The benchmark's own arrays, and the program's model file over them."""
        from variantcalling_tpu.models import registry

        self.weights_seed = weights_seed
        self.weights = self.family.arrays(weights_seed, self.config)
        self.models = os.path.join(self.dir, f"models_w{weights_seed}.pkl")
        registry.save_models(self.models, {
            self.config["model_name"]: self.family.to_program(self.config, self.weights)})

    # -- after the window -----------------------------------------------------

    def check(self) -> dict:
        """Every file of the window: exit code, record count, digest. Then a
        sample of each distinct output against the plain reference."""
        import numpy as np

        import fixtures
        import reference

        limits = self.config["limits"]
        sample_n = max(2, self.traffic["check_sample_per_file"] // self.divisor)
        groups: dict = {}
        for f in self.files:
            f["records"] = f["sha256"] = None
            if f["rc"] == 0 and os.path.exists(f["out"]):
                with open(f["out"], "rb") as fh:
                    data = fh.read()
                f["records"] = body_count(data)
                f["sha256"] = hashlib.sha256(data).hexdigest()
                groups[(f["ref"], f["sha256"])] = f
            f["failed"] = f["rc"] != 0 or f["records"] != self.n
        numbers = {"score_gap_max": 0.0, "filter_wrong": 0, "records_wrong": 0}
        detail = []
        for (ref_seed, _), f in sorted(groups.items(), key=lambda kv: kv[1]["i"]):
            ref = next(r for r in self.refs if r["seed"] == ref_seed)
            with open(ref["calls"], "rb") as fh:
                lines_in = body_lines(fh.read())
            with open(f["out"], "rb") as fh:
                lines_out = body_lines(fh.read())
            short = abs(len(lines_out) - len(lines_in))
            m = min(len(lines_in), len(lines_out))
            rng = np.random.default_rng([self.seed, ref_seed, 1])
            idx = np.unique(np.concatenate(
                [rng.choice(m, size=min(sample_n, m), replace=False), [0, m - 1]])) \
                if m else np.empty(0, np.int64)
            body = fixtures.contig_body(ref_seed, ref["genome_len"] // ref["n_contigs"])
            kw = dict(family=self.config["family"], weights=self.weights, body=body,
                      n_contigs=ref["n_contigs"], score_limit=limits["score_gap_max"])
            got = reference.compare([lines_in[j] for j in idx],
                                    [lines_out[j] for j in idx], **kw)
            got["records_wrong"] += short
            row = {"file": f["i"], "ref": ref_seed, **got}
            if self.control:
                row["control"] = reference.compare(
                    [lines_in[j] for j in idx], [], control=self.control, **kw)
            detail.append(row)
            for k in numbers:
                numbers[k] = max(numbers[k], got[k])
        compared = {k: {"value": numbers[k], "limit": limits[k]} for k in numbers}
        ok = bool(groups) and all(v["value"] <= v["limit"] for v in compared.values())
        return {"correct": ok, "compared": compared, "detail": detail}

    def finish(self) -> dict:
        """Device memory first (a peak never falls), then the checks, then the
        metrics this cell reports in this kind of run."""
        import jax

        devices = jax.devices()[: self.spec.cell["chips"]]
        stats = [d.memory_stats() or {} for d in devices]
        peak = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
        rss_gb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024 / 1e9
        t0 = time.perf_counter()
        checked = self.check()
        check_s = time.perf_counter() - t0
        attempted = len(self.files)
        failed = sum(f["failed"] for f in self.files)
        committed = sum(f["records"] for f in self.files if not f["failed"])
        device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
                  "count": len(jax.devices()), "memory_peak_bytes": peak}
        values = {"variants_per_s": committed / self.window_s,
                  "host_rss_peak_gb": rss_gb, "setup_s": self.setup_s}
        result = {"correct": checked["correct"] and failed == 0,
                  "attempted": attempted, "failed": failed}
        breakdown = None
        if self.trace:
            ctx = self.trace_context(device)
            device.update(busy_s=ctx["busy_s"], window_s=ctx["traced_s"])
            breakdown = ctx["breakdown"]
            values = {}
            for m in self.spec.per_layer:
                how = load_json(BENCH, "layer_metrics", m["name"] + ".json")
                v = reader_fn(how["reader"])(ctx, **how.get("args", {}))
                if v is not None:
                    values[m["name"]] = v
            names = self.spec.per_layer
        else:
            names = self.spec.end_to_end
        units = {m["name"]: m["unit"] for m in names}
        result["metrics"] = {k: {"value": v, "unit": units[k]}
                             for k, v in values.items() if k in units}
        result["device"] = device
        if breakdown:
            result["breakdown"] = breakdown
        result["compared"] = checked["compared"]
        self.details = {
            "cell": self.spec.cell["name"], "seed": self.seed,
            "weights_seed": self.weights_seed, "trace": self.trace,
            "seconds": self.seconds, "window_s": self.window_s, "check_s": check_s,
            "setup_s": self.setup_s, "host_rss_peak_gb": rss_gb,
            "files": [{k: v for k, v in f.items() if k != "out"} for f in self.files],
            "check": checked["detail"], "memory_stats": stats, "result": result}
        for f in self.files:
            for p in (f["out"], f["out"] + ".obs.jsonl"):
                if os.path.exists(p):
                    os.remove(p)
        return result

    def trace_context(self, device: dict) -> dict:
        """What the readers of per-layer metrics get to read."""
        import trace_reduce

        traced = [f for f in self.files if f["traced"]]
        events = []
        for f in traced:
            p = f["out"] + ".obs.jsonl"
            if os.path.exists(p):
                with open(p, encoding="utf-8") as fh:
                    events += [json.loads(ln) for ln in fh if ln.strip()]
        ctx = trace_reduce.reduce(self.trace_dir, n_chips=self.spec.cell["chips"],
                                  notes_path=os.path.join(OUT_DIR, "trace_lines.txt"),
                                  rehearsal=self.divisor > 1)
        window_events: dict = {}
        for f in self.files:
            for k, v in f["events"].items():
                window_events[k] = window_events.get(k, 0) + v
        ctx.update(
            obs_events=events, config=self.config, family=self.family,
            peaks=self.spec.peaks[device["kind"]],
            traced_s=self.traced["t1"] - self.traced["t0"],
            traced_rows=sum(f["records"] or 0 for f in traced),
            file_walls=[f["wall_s"] for f in self.files], n_files=len(self.files),
            compile_events=window_events, miss_event=MISS_EVENT, hit_event=HIT_EVENT)
        return ctx

    def go(self) -> dict:
        self.set_up()
        self.setup_s = time.perf_counter() - T_START
        say(f"set-up done in {self.setup_s:.1f}s; window of {self.seconds}s")
        self.driver.window(self)
        result = self.finish()
        self.report(result)
        return result

    def report(self, result: dict) -> None:
        os.makedirs(OUT_DIR, exist_ok=True)
        name = f"{self.spec.cell['name']}.seed{self.seed}.trace{int(self.trace)}.json"
        with open(os.path.join(OUT_DIR, name), "w", encoding="utf-8") as fh:
            json.dump(self.details, fh, indent=1)
        for f in self.details["files"]:
            say(f"file {f['i']} ref {f['ref']}: rc={f['rc']} {f['wall_s']:.2f}s "
                f"records={f['records']} events={f['events']}")
        say(f"window {self.window_s:.2f}s, {len(self.files)} files; check took "
            f"{self.details['check_s']:.1f}s; metrics "
            + json.dumps(result["metrics"]))
        for row in self.details["check"]:
            say("compared " + json.dumps(row))
        for k, v in result["compared"].items():
            print(f"{k} {v['value']!r} limit {v['limit']!r}", file=sys.stderr)
        sys.stderr.flush()


def prepare_env(spec: Spec, trace: bool) -> None:
    """Before JAX starts: the compilation cache inside the checkout (unless
    the machine names one), then whatever the configuration's ``env`` says."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(WORK, "jax_cache"))
    os.environ.update(spec.config.get("env", {}))
    os.environ["VCTPU_OBS"] = "1" if trace else "0"


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", type=int, default=0, metavar="DIVISOR",
                    help="sizes divided by DIVISOR on whatever JAX finds; never "
                         "prints a result line, always exits 4")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "variantcalling_tpu")):
        say(f"no program: {ROOT} holds no variantcalling_tpu/ - the benchmark "
            "drives the repo it sits in")
        return EXIT_NO_REPO
    spec = Spec(args.workload)
    seconds = args.seconds if args.seconds is not None else spec.run_seconds
    prepare_env(spec, bool(args.trace))

    import jax

    dev = jax.devices()
    if args.rehearse:
        if dev[0].platform != "tpu":
            os.environ.setdefault("VCTPU_ENGINE", "jit")
        spec.peaks.setdefault(dev[0].device_kind, next(iter(spec.peaks.values())))
    elif dev[0].platform != "tpu" or len(dev) < spec.cell["chips"]:
        say(f"no TPU with {spec.cell['chips']} chip(s): JAX initialized "
            f"{len(dev)} x {dev[0].platform} - nothing was run")
        return EXIT_NO_TPU
    elif dev[0].device_kind not in spec.peaks:
        say(f"device kind {dev[0].device_kind!r} is not in benchmarks/peaks.json")
        return EXIT_NO_TPU
    result = Run(spec, args.seed, seconds, bool(args.trace),
                 divisor=max(1, args.rehearse)).go()
    if args.rehearse:
        say("rehearsal, NOT a chip run, no result line: " + json.dumps(result))
        return EXIT_REHEARSAL
    print(json.dumps(result), flush=True)
    return 0  # the result line says whether the run was correct


if __name__ == "__main__":
    sys.exit(main())
