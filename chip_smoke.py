"""chip_smoke.py — the quickest proof that the filter path starts on the chip.

    python3 chip_smoke.py            # on a machine with a TPU (the chip tool)

Drives the system's main path once through the entry points a user calls
— ``python -m variantcalling_tpu filter_variants_pipeline`` (as
``variantcalling_tpu.__main__.main``) and an in-process ``vctpu serve``
daemon on a unix socket — at the full width of both model families the
repo serves (the benchmark's forest, T=40 depth 6; DAN E=16 H=256 L=2), with
seeded random weights and seeded inputs (1,000,000 variants, 10 Mbp, 4
contigs), and checks what comes out by the repo's own contracts:

- batch leg, one chip, three runs (set-up, settle, warm): exit 0,
  records out = records in, every TREE_SCORE finite in [0, 1],
  ``##vctpu_engine=jit``, the forest strategy ``auto`` resolves to, the
  streaming executor ran, no degradation from a device-path point, and
  the warm run neither misses nor grows the compile cache;
- correctness on the first 65,536 variants: the resolved strategy's
  margins equal the ``gather`` walk bit for bit on this chip, and the
  batch output agrees with it and with a ``VCTPU_ENGINE=native`` run;
- serve leg: three requests, each 200 and byte-equal to the batch output;
- DAN leg: the batch leg under ``VCTPU_MODEL_FAMILY=dan``;
- four-chip leg when >= 4 devices (it runs first, on untouched
  devices): ``##vctpu_mesh=dp=4``, bytes equal to the one-chip output
  modulo that line, all four devices shown to have held the replicated
  genome and run a shard.

One process, the only one that touches JAX. It exits non-zero and prints
no result line unless ``jax.devices()[0].platform == "tpu"``, and the same
in a directory that holds nothing else of the repo. The last line of
stdout is exactly one JSON object, ``{"ok": true, "device": {"platform":
..., "kind": ..., "count": ...}}``, with the device as JAX reports it. The
full summary (every leg's findings, ending ``"claim": null``) is the
stdout line before it and ``chiprun_out/chip_smoke/summary.json``, next
to the runs' obs logs. This script measures nothing: the walls in the
summary are smoke output (set-up vs warm), not benchmark numbers.

``--rehearse`` runs the same legs at a small size on whatever platform
JAX finds (the CPU, before spending chip time), prints its findings to
stderr and ALWAYS exits non-zero without a result line: a run that found
no TPU never prints a passing summary.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import re
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")
CHECK_ROWS = 65_536
MAX_DIFF_FRACTION = 0.001
EXIT_NO_TPU = 3
EXIT_REHEARSAL = 4
EXIT_NO_REPO = 5
#: degrade points on the device path — any event from one fails the run
DEVICE_POINTS = ("engine.", "forest.", "forest_pallas.", "shard_score.",
                 "compile_cache.", "obs.cost_analysis")


class SmokeFailure(AssertionError):
    pass


def require(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", file=sys.stderr, flush=True)


def body_lines(data: bytes) -> list[bytes]:
    return [ln for ln in data.split(b"\n") if ln and not ln.startswith(b"#")]


def header_lines(data: bytes) -> list[bytes]:
    """Header lines modulo ``##vctpu_*`` (engine, strategy, mesh, knobs:
    the only lines allowed to name the layout that produced the bytes)."""
    return [ln for ln in data.split(b"\n")
            if ln.startswith(b"#") and not ln.startswith(b"##vctpu_")]


def tree_scores(data: bytes):
    import numpy as np

    return np.array(re.findall(rb"TREE_SCORE=([^;\t\n]+)", data),
                    dtype=np.float64)


def header_value(data: bytes, key: str) -> str | None:
    m = re.search(rb"^##" + key.encode() + rb"=(.*)$", data, re.M)
    return m.group(1).decode() if m else None


def obs_events(path: str) -> list[dict]:
    with open(path, encoding="utf-8") as fh:
        return [json.loads(ln) for ln in fh if ln.strip()]


def cache_entries() -> set[str]:
    from variantcalling_tpu.utils.compile_cache import cache_dir

    d = cache_dir()
    return set(os.listdir(d)) if os.path.isdir(d) else set()


class CompileWatch:
    """What JAX compiled and what its persistent cache answered, per leg:
    JAX's own compile/cache debug lines go to ``jax_compiles.log`` in the
    output directory (not to stderr), and the compilation-cache hit/miss
    events are counted."""

    LOGGERS = ("jax._src.dispatch", "jax._src.interpreters.pxla",
               "jax._src.compiler", "jax._src.compilation_cache")

    def __init__(self) -> None:
        import logging

        import jax

        self.counts: dict[str, int] = {}
        handler = logging.FileHandler(os.path.join(OUT_DIR, "jax_compiles.log"),
                                      mode="w")
        handler.setFormatter(logging.Formatter("%(asctime)s %(threadName)s %(message)s"))
        for name in self.LOGGERS:
            log = logging.getLogger(name)
            log.setLevel(logging.DEBUG)
            log.addHandler(handler)
            log.propagate = False
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        if "compilation_cache" in event:
            key = event.rsplit("/", 1)[-1]
            self.counts[key] = self.counts.get(key, 0) + 1

    def take(self) -> dict[str, int]:
        out, self.counts = dict(sorted(self.counts.items())), {}
        return out


class Smoke:
    def __init__(self, work: str, n: int, genome: int):
        self.work, self.n, self.genome = work, n, genome
        self.compiles = CompileWatch()
        self.vcf = os.path.join(work, "calls.vcf")
        self.ref = os.path.join(work, "ref.fa")
        self.models = os.path.join(work, "models.pkl")
        self.summary: dict = {}

    # -- set-up -------------------------------------------------------------

    def make_inputs(self) -> None:
        import numpy as np

        from variantcalling_tpu.featurize import BASE_FEATURES
        from variantcalling_tpu.models import registry
        from variantcalling_tpu.synthetic import DEPTH, N_TREES, make_fixtures_fast, synthetic_dan, synthetic_forest

        make_fixtures_fast(self.work, n=self.n, genome_len=self.genome)
        self.forest = synthetic_forest(np.random.default_rng(0),
                                       n_trees=N_TREES, depth=DEPTH)
        dan = synthetic_dan(np.random.default_rng(1), list(BASE_FEATURES),
                            embed_dim=16, hidden=256, n_layers=2)
        registry.save_models(self.models, {"forest": self.forest, "dan": dan})

    # -- one CLI run --------------------------------------------------------

    def cli(self, out: str, model_name: str = "forest") -> tuple[bytes, float]:
        from variantcalling_tpu.__main__ import main

        t0 = time.perf_counter()
        rc = main(["filter_variants_pipeline", "--input_file", self.vcf,
                   "--model_file", self.models, "--model_name", model_name,
                   "--reference_file", self.ref, "--output_file", out])
        wall = time.perf_counter() - t0
        # the run's obs log goes home whatever the checks below decide
        name = os.path.basename(out)[:-len(".vcf")] + ".obs.jsonl"
        if os.path.exists(out + ".obs.jsonl"):
            shutil.move(out + ".obs.jsonl", os.path.join(OUT_DIR, name))
        require(rc == 0, f"filter_variants_pipeline exited {rc} ({out})")
        with open(out, "rb") as fh:
            return fh.read(), wall

    def check_output(self, data: bytes, label: str) -> dict:
        import numpy as np

        n_out = len(body_lines(data))
        require(n_out == self.n, f"{label}: {n_out} records out, {self.n} in")
        scores = tree_scores(data)
        require(len(scores) == self.n, f"{label}: {len(scores)} TREE_SCOREs")
        require(bool(np.isfinite(scores).all()
                     and (scores >= 0).all() and (scores <= 1).all()),
                f"{label}: TREE_SCORE outside finite [0, 1]")
        require(header_value(data, "vctpu_engine") == "jit",
                f"{label}: engine header is "
                f"{header_value(data, 'vctpu_engine')!r}, not 'jit'")
        return {"records": n_out, "score_mean": round(float(scores.mean()), 6)}

    def check_streaming(self, obs_log: str, label: str) -> None:
        starts = [e for e in obs_events(obs_log)
                  if e.get("kind") == "stage" and e.get("name") == "pipeline_start"]
        modes = {e.get("mode") for e in starts}
        require(modes == {"threaded"},
                f"{label}: run mode is not 'streaming' (executor modes "
                f"{sorted(map(str, modes))}; native.available() false?)")

    # -- legs ---------------------------------------------------------------

    def batch_leg(self) -> bytes:
        from variantcalling_tpu.models import forest as forest_mod

        os.environ["VCTPU_MESH_DEVICES"] = "1"
        expect = forest_mod.resolve_strategy(self.forest)
        out1 = os.path.join(self.work, "batch1.vcf")
        data1, setup_s = self.cli(out1)
        info = self.check_output(data1, "batch run 1")
        strategy = header_value(data1, forest_mod.STRATEGY_HEADER_KEY)
        require(strategy == expect,
                f"header strategy {strategy!r} is not what auto resolves to "
                f"here ({expect!r})")
        require(header_value(data1, "vctpu_mesh") is None,
                "one-chip run carries a ##vctpu_mesh line")
        self.check_streaming(os.path.join(OUT_DIR, "batch1.obs.jsonl"), "batch run 1")
        # Run 2 may still compile: whether a chunk under
        # featurize.GENOME_RESIDENT_MIN_VARIANTS gathers its windows from
        # the device-resident genome depends on whether the genome is
        # ALREADY resident, so the input's short tail chunk changes
        # program layout once the first run has uploaded it. Run 3 sees
        # the same process state as run 2 and must be all cache hits.
        seen = cache_entries()
        events = [self.compiles.take()]
        added, walls = [], [setup_s]
        for i in (2, 3):
            out = os.path.join(self.work, f"batch{i}.vcf")
            data, wall = self.cli(out)
            os.remove(out)
            require(data == data1, f"batch run {i}'s bytes differ from run 1's")
            now = cache_entries()
            added.append(sorted(now - seen))
            seen = now
            events.append(self.compiles.take())
            walls.append(wall)
        self.summary["batch"] = dict(
            info, strategy=strategy, mode="streaming",
            sha256=hashlib.sha256(data1).hexdigest(),
            setup_s_smoke_output=round(walls[0], 2),
            settle_s_smoke_output=round(walls[1], 2),
            warm_s_smoke_output=round(walls[2], 2),
            compile_cache_entries=len(seen),
            cache_entries_added_by_run2=added[0],
            cache_entries_added_by_run3=added[1],
            compile_cache_events_by_run=events)
        require(not added[1],
                f"the warm batch run added compile-cache entries: {added[1][:5]}")
        require(not events[2].get("cache_misses"),
                f"the warm batch run missed the compile cache: {events[2]}")
        setup_s, warm_s = walls[0], walls[2]
        say(f"batch: strategy={strategy} set-up {setup_s:.1f}s warm {warm_s:.1f}s "
            "(smoke output, not measurements)")
        return data1

    def correctness(self, batch: bytes) -> None:
        """Resolved strategy vs the gather walk on this chip, bit for bit
        (the repo's strategy contract), and the batch output against the
        gather walk's finalized scores."""
        import numpy as np

        import jax
        import jax.numpy as jnp

        from variantcalling_tpu.featurize import host_featurize, materialize_features
        from variantcalling_tpu.io.fasta import FastaReader
        from variantcalling_tpu.io.vcf import read_vcf
        from variantcalling_tpu.models import dan as dan_mod
        from variantcalling_tpu.models import forest as forest_mod
        from variantcalling_tpu.models import registry

        table = read_vcf(self.vcf)
        rows = min(CHECK_ROWS, len(table))
        table = table.subset(np.arange(len(table)) < rows)
        fs = materialize_features(host_featurize(table, FastaReader(self.ref)),
                                  flow_order="TGCA")
        x = jnp.asarray(fs.matrix())
        ordered = forest_mod.with_feature_order(self.forest, fs.feature_names)
        strategy = self.summary["batch"]["strategy"]
        got = np.asarray(jax.jit(forest_mod.make_margin_predictor(
            ordered, x.shape[1], strategy=strategy))(x))
        ref = np.asarray(jax.jit(
            lambda xx: forest_mod.predict_margin(ordered, xx))(x))
        require(got.tobytes() == ref.tobytes(),
                f"{strategy} margins differ from the gather walk on this chip "
                f"(max abs diff {np.abs(got - ref).max()})")
        want = np.round(forest_mod.finalize_margin(ref, ordered), 4)
        wrote = tree_scores(b"\n".join(body_lines(batch)[:rows]))
        off = int((np.abs(wrote - want) > 1e-4).sum())
        require(off <= MAX_DIFF_FRACTION * rows,
                f"{off}/{rows} output TREE_SCOREs disagree with the gather walk")
        self.summary["correctness"] = {
            "rows": rows, "strategy": strategy, "margins_bit_equal_gather": True,
            "output_scores_off_gather": off}
        # DAN: default matmul precision against "highest" on the same rows
        dan = registry.load_model(self.models, "dan")
        fwd = dan_mod.make_score_predictor(dan, fs.feature_names)
        s_default = np.asarray(jax.jit(fwd)(x))
        with jax.default_matmul_precision("highest"):
            s_highest = np.asarray(jax.jit(fwd)(x))
        self.summary["dan_precision"] = {
            "rows": rows,
            "max_abs_diff_default_vs_highest": float(np.abs(s_default - s_highest).max())}
        say(f"correctness: {strategy} == gather bit for bit on {rows} rows; "
            f"output scores off gather: {off}; DAN default-vs-highest max diff "
            f"{self.summary['dan_precision']['max_abs_diff_default_vs_highest']:.3g}")

    def serve_leg(self, batch: bytes) -> None:
        from variantcalling_tpu.serve import transport
        from variantcalling_tpu.serve.daemon import Server

        sock = os.path.join(self.work, "serve.sock")
        server = Server(socket_path=sock,
                        obs_log=os.path.join(OUT_DIR, "serve.obs.jsonl"))
        server.start()
        try:
            def post(out: str, **extra) -> dict:
                body = {"input": self.vcf, "model": self.models,
                        "model_name": "forest", "reference": self.ref,
                        "output": out, **extra}
                with transport.request(
                        sock, "POST", "/v1/filter",
                        headers={"Content-Type": "application/json"},
                        body=json.dumps(body).encode(), timeout=900) as r:
                    payload = r.json()
                    require(r.status == 200 and payload.get("status") == "ok",
                            f"serve request answered {r.status}: {payload}")
                return payload

            walls = []
            for i in range(3):
                out = os.path.join(self.work, f"serve{i}.vcf")
                payload = post(out)
                require(payload.get("engine") == "jit",
                        f"serve request scored on {payload.get('engine')!r}")
                with open(out, "rb") as fh:
                    require(fh.read() == batch,
                            f"serve request {i} is not byte-equal to the batch output")
                os.remove(out)
                walls.append(round(payload["dur_s"], 2))
            # the same input under the native C++ engine, pinned per request
            out = os.path.join(self.work, "serve_native.vcf")
            payload = post(out, knobs={"VCTPU_ENGINE": "native"})
            require(payload.get("engine") == "native", "native request did not pin")
            with open(out, "rb") as fh:
                native = fh.read()
            os.remove(out)
        finally:
            server.drain("chip_smoke")
        a, b = body_lines(batch), body_lines(native)
        require(len(a) == len(b), "native run wrote a different record count")
        differ = sum(x != y for x, y in zip(a, b))
        headers_equal = header_lines(batch) == header_lines(native)
        require(differ <= MAX_DIFF_FRACTION * self.n,
                f"{differ}/{self.n} records differ from the native engine's output")
        self.summary["serve"] = {"requests": 3, "byte_equal_batch": True,
                                 "dur_s_smoke_output": walls}
        self.summary["native_engine"] = {
            "records_differing": differ, "headers_equal_modulo_vctpu": headers_equal}
        say(f"serve: 3/3 byte-equal to batch; native engine: {differ} of "
            f"{self.n} records differ")

    def dan_leg(self) -> None:
        os.environ["VCTPU_MODEL_FAMILY"] = "dan"
        try:
            out = os.path.join(self.work, "dan.vcf")
            data, wall = self.cli(out, model_name="dan")
        finally:
            del os.environ["VCTPU_MODEL_FAMILY"]
        info = self.check_output(data, "dan run")
        require(header_value(data, "vctpu_model_family") == "dan",
                "dan run lacks ##vctpu_model_family=dan")
        os.remove(out)
        self.summary["dan"] = dict(info, wall_s_smoke_output=round(wall, 2),
                                   **self.summary.pop("dan_precision"))
        say(f"dan: ok, wall {wall:.1f}s (smoke output)")

    def four_chip_leg(self) -> bytes | None:
        """Runs FIRST on a host with >= 4 devices, while nothing has
        touched them: ``peak_bytes_in_use`` is a process-lifetime maximum,
        so only before the one-chip legs (which load device 0) do all
        four per-device peaks describe this leg alone."""
        import jax

        from variantcalling_tpu import featurize

        devices = jax.devices()
        if len(devices) < 4:
            self.summary["four_chip"] = f"skipped: {len(devices)} device(s)"
            return None
        os.environ["VCTPU_MESH_DEVICES"] = "4"
        out = os.path.join(self.work, "dp4.vcf")
        data, wall = self.cli(out)
        os.remove(out)
        self.check_output(data, "dp=4 run")
        require(header_value(data, "vctpu_mesh") == "dp=4",
                f"dp=4 run header says {header_value(data, 'vctpu_mesh')!r}")
        # the genome: resident once, replicated over the four devices
        resident = [g for k, g in featurize._DEVICE_GENOME_CACHE.items()
                    if k[0] == self.ref and len(g.rows.sharding.device_set) == 4]
        require(len(resident) == 1,
                f"{len(resident)} four-device genome entries resident (want 1: "
                "uploaded once, not per megabatch)")
        rows = resident[0].rows
        require(rows.sharding.is_fully_replicated,
                f"genome sharding {rows.sharding} is not replicated")
        genome_bytes = int(rows.nbytes)
        # evidence that all four chips held and ran a shard: a peak well
        # above the replicated genome is shard inputs + kernel temporaries
        peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                 for d in devices[:4]]
        if devices[0].platform == "tpu":
            require(all(p > genome_bytes + (1 << 20) for p in peaks),
                    f"per-device peak bytes {peaks} not all 1 MiB above the "
                    f"replicated genome ({genome_bytes} B)")
        self.summary["four_chip"] = {
            "mesh": "dp=4", "genome_replicated_once": True,
            "genome_bytes": genome_bytes,
            "evidence": "memory_stats peak_bytes_in_use per device, read "
                        "before any other leg touched the devices",
            "peak_bytes_in_use": peaks, "wall_s_smoke_output": round(wall, 2),
            "compile_cache_events": self.compiles.take()}
        say(f"four-chip: dp=4 ran; per-device peaks {peaks}")
        return data

    def four_chip_compare(self, dp4: bytes | None, batch: bytes) -> None:
        if dp4 is None:
            return
        require([ln for ln in dp4.split(b"\n") if ln != b"##vctpu_mesh=dp=4"]
                == batch.split(b"\n"),
                "dp=4 output differs from the one-chip output beyond the mesh line")
        self.summary["four_chip"]["bytes_equal_one_chip_modulo_mesh_line"] = True
        say("four-chip: bytes equal to the one-chip output modulo the mesh line")

    def check_degradations(self) -> None:
        from variantcalling_tpu.utils import degrade

        events = list(degrade.EVENTS)
        device = [e for e in events if e[0].startswith(DEVICE_POINTS)]
        for e in events:
            say(f"degradation recorded: {e}")
        require(not device, f"device-path degradations: {device}")
        self.summary["degradations"] = {
            "device_path": 0, "other": sorted({e[0] for e in events})}

    def run(self) -> None:
        self.make_inputs()
        dp4 = self.four_chip_leg()
        batch = self.batch_leg()
        self.four_chip_compare(dp4, batch)
        self.correctness(batch)
        self.serve_leg(batch)
        self.dan_leg()
        self.check_degradations()


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="small sizes on whatever platform JAX finds; never "
                         "prints a result line, always exits non-zero")
    ap.add_argument("--variants", type=int, default=None,
                    help="input size (the genome is 10 bp per variant)")
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()
    device = {"platform": dev[0].platform, "kind": dev[0].device_kind,
              "count": len(dev)}
    if device["platform"] != "tpu" and not args.rehearse:
        say(f"no TPU: JAX initialized {device} — nothing was run")
        return EXIT_NO_TPU
    if not os.path.isdir(os.path.join(HERE, "variantcalling_tpu")):
        say(f"no program: {HERE} holds no variantcalling_tpu/ — this script "
            "drives the repo it sits in, and proves nothing alone")
        return EXIT_NO_REPO
    if args.rehearse:
        # the chip resolves engine=jit by itself; a CPU only exercises the
        # same legs when asked to
        os.environ.setdefault("VCTPU_ENGINE", "jit")
    n = args.variants or (250_000 if args.rehearse else 1_000_000)
    genome = 10 * n  # 1,000,000 variants on 10 Mbp, 4 contigs

    sys.path.insert(0, HERE)
    os.makedirs(OUT_DIR, exist_ok=True)
    os.environ["VCTPU_OBS"] = "1"
    t0 = time.perf_counter()
    failure = None
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as work:
        smoke = Smoke(work, n, genome)
        try:
            smoke.run()
        except SmokeFailure as e:
            failure = str(e)
            say(f"FAILED: {failure}")
    summary = {"ok": failure is None, "device": device, "variants": n,
               "genome_bp": genome, **smoke.summary,
               "total_s_smoke_output": round(time.perf_counter() - t0, 1)}
    if failure is not None:
        summary["failure"] = failure
    summary["claim"] = None
    if args.rehearse:
        say(("rehearsal passed" if failure is None else "rehearsal FAILED")
            + " — NOT a chip run, no result line: "
            + json.dumps(dict(summary, ok=False)))
        return EXIT_REHEARSAL
    with open(os.path.join(OUT_DIR, "summary.json"), "w") as fh:
        json.dump(summary, fh, indent=1)
    print(json.dumps(summary), flush=True)
    # the result line: exactly these keys, the device as JAX reports it
    print(json.dumps({"ok": failure is None, "device": device}), flush=True)
    return 0 if failure is None else 1


if __name__ == "__main__":
    sys.exit(main())
