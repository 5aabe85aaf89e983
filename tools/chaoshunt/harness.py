"""Campaign engine for ``tools.chaoshunt`` (see package docstring).

Every leg is a SUBPROCESS running the real CLI entry
(``pipelines/filter_variants.run``) against small synthetic fixtures
(``synthetic.make_fixtures``), with the schedule's faults armed through
``VCTPU_FAULTS`` (the env grammar exists precisely so harnesses need no
test API) and the layout pinned through the knob registry. A tiny driver
wrapper maps exceptions to exit code 1, then self-reports leaked
``vctpu-*``/``pipe-*`` threads into a status JSON — the one invariant an
exit code cannot carry.
"""

from __future__ import annotations

import dataclasses
import json
import os
import random
import signal
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

#: fault points a schedule may draw (site descriptions: utils/faults.py)
TRANSIENT_POINTS = ("io.chunk_read", "io.writeback", "pipeline.stage",
                    "pipeline.chunk")
PERSISTENT_POINTS = ("io.writeback", "pipeline.stage", "pipeline.chunk",
                     "io.chunk_read")
LAYOUTS = ("serial", "io4", "mesh2")

#: wall bound per child process (imports jax; the run itself is seconds)
CHILD_TIMEOUT_S = 240

_DRIVER = """\
import json, sys, threading, time
cfg = json.load(open(sys.argv[1]))
if cfg.get("sabotage"):
    exec(compile(open(cfg["sabotage"]).read(), "sabotage", "exec"), {})
from variantcalling_tpu.pipelines.filter_variants import run
err = None
try:
    rc = run(["--input_file", cfg["input"], "--model_file", cfg["model"],
              "--model_name", "m", "--reference_file", cfg["ref"],
              "--output_file", cfg["out"], "--backend", "cpu"])
except SystemExit as e:
    rc = int(e.code or 0)
except BaseException as e:
    rc, err = 1, f"{type(e).__name__}: {e}"
def _leaked():
    return sorted(t.name for t in threading.enumerate()
                  if t.name.startswith(("vctpu-", "pipe-", "genome-prefetch")))
deadline = time.time() + 3.0
leaked = _leaked()
while leaked and time.time() < deadline:
    time.sleep(0.05)
    leaked = _leaked()
json.dump({"rc": rc, "error": err, "leaked": leaked},
          open(cfg["status"], "w"))
raise SystemExit(rc)
"""


@dataclasses.dataclass
class FaultSpec:
    """One armed fault of a schedule (``utils/faults.py`` env grammar)."""

    point: str
    times: int | None = 1  # None == unlimited (persistent)
    seconds: float | None = None  # delay/hang length
    after: int = 0  # free passes before the first firing

    def spec(self) -> str:
        s = self.point
        s += f":{0 if self.times is None else self.times}"
        if self.seconds is not None:
            s += f"@{self.seconds}"
        if self.after:
            s += f"+{self.after}"
        return s

    def to_json(self) -> dict:
        return dataclasses.asdict(self)

    @staticmethod
    def from_json(d: dict) -> "FaultSpec":
        return FaultSpec(point=d["point"], times=d.get("times"),
                         seconds=d.get("seconds"),
                         after=int(d.get("after", 0)))


@dataclasses.dataclass
class Schedule:
    """One drawn chaos schedule: layout x faults x optional SIGKILL
    (single-process), a ``rank_kill`` pod schedule — SIGKILL one worker
    rank of a 2-rank local-launcher run mid-stream — or an ``elastic``
    pod schedule against ``tools/podrun --elastic`` (docs/scaleout.md
    "Elastic membership")."""

    seed: int
    layout: str  # serial | io4 | mesh2
    faults: list[FaultSpec] = dataclasses.field(default_factory=list)
    kill_after_chunks: int | None = None  # SIGKILL once N chunks journaled
    #: pod fault class: {"ranks": N, "kill_rank": r, "after_chunks": k}
    rank_kill: dict | None = None
    #: elastic pod fault class (docs/scaleout.md "Elastic membership"):
    #: {"mode": "rank_flap", "ranks": 2, "kills": k, "after_chunks": c}
    #: (SIGKILL k span workers mid-journal — the coordinator must re-cut
    #: and finish IN THE SAME LAUNCH), {"mode": "steal_race"} or
    #: {"mode": "join_during_merge"} (the launcher's built-in duplicate-
    #: claimant drills — the lease must refuse the second renderer)
    elastic: dict | None = None
    #: chunk-cache fault class (docs/caching.md): {"mode": "poison"}
    #: (bit-flipped entry bodies) or {"mode": "torn"} (SIGKILL inside an
    #: entry write) — the cache must recompute, never serve wrong bytes
    cache: dict | None = None

    def faults_env(self) -> str:
        return ",".join(f.spec() for f in self.faults)

    def to_json(self) -> dict:
        return {"seed": self.seed, "layout": self.layout,
                "faults": [f.to_json() for f in self.faults],
                "kill_after_chunks": self.kill_after_chunks,
                "rank_kill": self.rank_kill,
                "cache": self.cache,
                "elastic": self.elastic}

    @staticmethod
    def from_json(d: dict) -> "Schedule":
        return Schedule(seed=int(d.get("seed", 0)),
                        layout=d.get("layout", "serial"),
                        faults=[FaultSpec.from_json(f)
                                for f in d.get("faults", [])],
                        kill_after_chunks=d.get("kill_after_chunks"),
                        rank_kill=d.get("rank_kill"),
                        cache=d.get("cache"),
                        elastic=d.get("elastic"))

    def describe(self) -> str:
        parts = [self.layout]
        if self.faults:
            parts.append(self.faults_env())
        if self.kill_after_chunks is not None:
            parts.append(f"SIGKILL@{self.kill_after_chunks}ch")
        if self.rank_kill is not None:
            parts.append(f"rank_kill r{self.rank_kill['kill_rank']}"
                         f"/{self.rank_kill['ranks']}"
                         f"@{self.rank_kill['after_chunks']}ch")
        if self.cache is not None:
            parts.append(f"cache_{self.cache['mode']}")
        if self.elastic is not None:
            s = f"elastic_{self.elastic['mode']}"
            if self.elastic["mode"] == "rank_flap":
                s += (f" x{self.elastic.get('kills', 1)}"
                      f"@{self.elastic.get('after_chunks', 1)}ch")
            parts.append(s)
        return " ".join(parts)


def draw_schedule(seed: int) -> Schedule:
    """Deterministic schedule for one seed: a layout (cycled so every
    third seed covers each of serial/io4/mesh2) plus one fault class —
    transient, persistent, hang (short delays, or a long cancellable
    hang the v2 watchdog must recover), device-OOM (mesh only),
    commit-ENOSPC, or a SIGKILL-at-random-progress leg."""
    rng = random.Random(seed)
    layout = LAYOUTS[seed % len(LAYOUTS)]
    modes = ["transient", "persistent", "hang", "kill", "commit", "mixed",
             "rank_kill"]
    if layout == "mesh2":
        # the mesh megabatch layout bypasses the chunk cache, so cache
        # fault classes are drawn on the host layouts only — and the
        # elastic pod classes ride the host layouts too (every span
        # worker of a mesh pod would multiply the process budget)
        modes.append("oom")
    else:
        modes += ["cache_poison", "cache_torn",
                  "rank_flap", "steal_race", "join_during_merge"]
    mode = rng.choice(modes)
    faults: list[FaultSpec] = []
    kill = None
    rank_kill = None
    if mode in ("cache_poison", "cache_torn"):
        return Schedule(seed=seed, layout=layout,
                        cache={"mode": mode.removeprefix("cache_")})
    if mode == "rank_flap":
        # elastic membership churn: SIGKILL k span workers, each only
        # after ITS journal shows progress — the coordinator must re-cut
        # at the watermark and commit in the SAME launch. A persistent
        # per-chunk delay keeps every worker mid-stream long enough.
        faults.append(FaultSpec("pipeline.stage_hang", times=None,
                                seconds=0.2))
        return Schedule(seed=seed, layout=layout, faults=faults,
                        elastic={"mode": "rank_flap", "ranks": 2,
                                 "kills": rng.randint(1, 2),
                                 "after_chunks": rng.randint(1, 2)})
    if mode in ("steal_race", "join_during_merge"):
        return Schedule(seed=seed, layout=layout,
                        elastic={"mode": mode, "ranks": 2})
    if mode == "rank_kill":
        # pod fault class (docs/scaleout.md): a 2-rank local-launcher
        # run; one worker rank is SIGKILLed once its SEGMENT journal
        # shows progress. A persistent per-chunk delay keeps every rank
        # mid-stream long enough for the kill to land mid-run.
        rank_kill = {"ranks": 2, "kill_rank": rng.randint(0, 1),
                     "after_chunks": rng.randint(1, 2)}
        faults.append(FaultSpec("pipeline.stage_hang", times=None,
                                seconds=0.05))
        return Schedule(seed=seed, layout=layout, faults=faults,
                        rank_kill=rank_kill)
    if mode == "transient":
        for _ in range(rng.randint(1, 2)):
            faults.append(FaultSpec(rng.choice(TRANSIENT_POINTS),
                                    times=rng.randint(1, 2),
                                    after=rng.randint(0, 2)))
    elif mode == "persistent":
        faults.append(FaultSpec(rng.choice(PERSISTENT_POINTS), times=None,
                                after=rng.randint(0, 3)))
    elif mode == "hang":
        if rng.random() < 0.5:
            # short per-chunk delays: progress slows, nothing trips
            faults.append(FaultSpec("pipeline.stage_hang",
                                    times=rng.randint(1, 3),
                                    seconds=round(rng.uniform(0.1, 0.4), 2)))
        else:
            # one LONG cancellable hang: the v2 watchdog must dump, cancel
            # and recover the run (VCTPU_STAGE_TIMEOUT_S=2 below)
            faults.append(FaultSpec("pipeline.stage_hang", times=1,
                                    seconds=30,
                                    after=rng.randint(0, 2)))
    elif mode == "kill":
        kill = rng.randint(1, 3)
        if rng.random() < 0.5:  # slow the chunks so the kill lands mid-run
            faults.append(FaultSpec("pipeline.stage_hang", times=None,
                                    seconds=0.1))
    elif mode == "commit":
        faults.append(FaultSpec("io.commit",
                                times=rng.choice([1, None])))
    elif mode == "oom":
        faults.append(FaultSpec("xla.dispatch_oom",
                                times=rng.choice([1, 2, None]),
                                after=rng.randint(0, 1)))
    else:  # mixed: a transient plus a persistent or a kill
        faults.append(FaultSpec(rng.choice(TRANSIENT_POINTS),
                                times=rng.randint(1, 2)))
        if rng.random() < 0.5:
            faults.append(FaultSpec(rng.choice(PERSISTENT_POINTS),
                                    times=None, after=rng.randint(1, 4)))
        else:
            kill = rng.randint(1, 3)
    return Schedule(seed=seed, layout=layout, faults=faults,
                    kill_after_chunks=kill)


# ---------------------------------------------------------------------------
# fixtures + reference
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Fixtures:
    dir: str
    input_vcf: str
    model: str
    ref: str
    reference_norm: bytes  # normalized clean-run output bytes


def normalize_output(data: bytes) -> bytes:
    """Strip the ``##vctpu_*`` provenance header lines that legitimately
    differ across engine/strategy/mesh/rank layouts — record bytes are
    identical by the byte-parity contract, so these lines are the ONLY
    tolerated delta. The ONE normalization spelling (prefix, not an
    enumerated list — a NEW provenance line must never silently diverge
    the comparators), shared by loadhunt and the scale-out test suites."""
    return b"\n".join(
        ln for ln in data.split(b"\n")
        if not ln.startswith(b"##vctpu_"))


def _layout_env(layout: str) -> dict:
    if layout == "serial":
        return {"VCTPU_IO_THREADS": "1"}
    if layout == "io4":
        return {"VCTPU_IO_THREADS": "4"}
    if layout == "mesh2":
        return {"VCTPU_IO_THREADS": "4", "VCTPU_MESH_DEVICES": "2",
                "VCTPU_ENGINE": "jit",
                "XLA_FLAGS": "--xla_force_host_platform_device_count=2"}
    raise ValueError(f"unknown layout {layout!r}")


def _child_env(layout: str, faults_spec: str = "",
               extra_env: dict | None = None) -> dict:
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("VCTPU_") and k not in ("XLA_FLAGS",
                                                       "PYTHONPATH")}
    env.update(PYTHONPATH=REPO, JAX_PLATFORMS="cpu",
               VCTPU_STREAM_CHUNK_BYTES=str(1 << 14),
               VCTPU_IO_BACKOFF_S="0.01",
               VCTPU_STAGE_TIMEOUT_S="2",
               # pin the compute pool: streaming eligibility must not
               # depend on the host's core count (1-CPU runners would
               # silently divert every leg onto the batch path)
               VCTPU_THREADS="2")
    env.update(_layout_env(layout))
    if faults_spec:
        env["VCTPU_FAULTS"] = faults_spec
    if extra_env:
        env.update(extra_env)
    return env


def build_fixtures(workdir: str, records: int = 2000,
                   model_family: str = "forest") -> Fixtures:
    """Synthesize the input set once per campaign and produce the clean
    byte reference (a fault-free, SABOTAGE-free serial-layout child run —
    the oracle models the known-good behavior, so a ``--sabotage``
    regression applies only to the legs under test).

    ``model_family`` picks the scoring model the campaign pickles —
    "forest" (the default) or "dan" (docs/models.md): the recovery
    ladder's invariants are family-independent by contract, so the same
    schedules must hold whichever family scored."""
    import pickle

    import numpy as np

    from variantcalling_tpu.synthetic import make_fixtures, synthetic_dan, synthetic_forest

    d = os.path.join(workdir, "fixtures")
    os.makedirs(d, exist_ok=True)
    make_fixtures(d, n=records, genome_len=150_000)
    if model_family == "dan":
        from variantcalling_tpu.featurize import BASE_FEATURES

        model = synthetic_dan(np.random.default_rng(0), BASE_FEATURES)
    else:
        model = synthetic_forest(np.random.default_rng(0), n_trees=8,
                                 depth=4)
    with open(os.path.join(d, "model.pkl"), "wb") as fh:
        pickle.dump({"m": model}, fh)
    fx = Fixtures(dir=d, input_vcf=os.path.join(d, "calls.vcf"),
                  model=os.path.join(d, "model.pkl"),
                  ref=os.path.join(d, "ref.fa"), reference_norm=b"")
    out = os.path.join(d, "reference.vcf")
    leg = run_leg(fx, out, "serial", "", None)
    if leg["rc"] != 0:
        raise RuntimeError(
            f"chaoshunt: the fault-free reference run failed (rc={leg['rc']})"
            + (f": {leg['status'].get('error')}" if leg.get("status") else ""))
    fx.reference_norm = normalize_output(open(out, "rb").read())
    return fx


# ---------------------------------------------------------------------------
# one leg = one subprocess run
# ---------------------------------------------------------------------------


def run_leg(fx: Fixtures, out: str, layout: str, faults_spec: str,
            kill_after_chunks: int | None,
            sabotage: str | None = None,
            extra_env: dict | None = None) -> dict:
    """Run the filter CLI once in a subprocess; returns the leg record
    (rc, killed, status, sidecar presence)."""
    status_path = out + ".chaos_status.json"
    cfg_path = out + ".chaos_cfg.json"
    with open(cfg_path, "w", encoding="utf-8") as fh:
        json.dump({"input": fx.input_vcf, "model": fx.model, "ref": fx.ref,
                   "out": out, "status": status_path,
                   "sabotage": sabotage}, fh)
    env = _child_env(layout, faults_spec, extra_env)
    argv = [sys.executable, "-c", _DRIVER, cfg_path]
    killed = False
    if kill_after_chunks is None:
        proc = subprocess.run(argv, env=env, cwd=REPO,  # noqa: S603
                              capture_output=True, text=True,
                              timeout=CHILD_TIMEOUT_S)
        rc: int | None = proc.returncode
        stderr = proc.stderr[-4000:]
    else:
        # SIGKILL-at-progress leg: watch the journal grow, then kill.
        # Bounded: if the child finishes (or stalls) first, fall through.
        p = subprocess.Popen(argv, env=env, cwd=REPO,  # noqa: S603
                             stdout=subprocess.DEVNULL,
                             stderr=subprocess.DEVNULL)
        jpath = out + ".journal"
        deadline = time.time() + CHILD_TIMEOUT_S
        try:
            while time.time() < deadline and p.poll() is None:
                try:
                    with open(jpath, encoding="utf-8") as fh:
                        committed = max(0, len(fh.read().splitlines()) - 1)
                except OSError:
                    committed = 0
                if committed >= kill_after_chunks:
                    os.kill(p.pid, signal.SIGKILL)
                    killed = True
                    break
                time.sleep(0.02)
        finally:
            if p.poll() is None and not killed:
                os.kill(p.pid, signal.SIGKILL)
                killed = True
            p.wait(timeout=30)
        rc = None if killed else p.returncode
        stderr = ""
    status = None
    try:
        with open(status_path, encoding="utf-8") as fh:
            status = json.load(fh)
    except (OSError, ValueError):
        status = None
    for p_ in (status_path, cfg_path):
        try:
            os.remove(p_)
        except OSError:
            pass
    from variantcalling_tpu.io import journal as journal_mod

    return {"rc": rc, "killed": killed, "status": status, "stderr": stderr,
            "out_exists": os.path.exists(out),
            # unique-suffix partials (ISSUE 14): any <out>.partial* counts
            "partial": bool(journal_mod.list_partials(out)),
            "journal": os.path.exists(out + ".journal"),
            "quarantine": os.path.exists(out + ".quarantine")}


def _check_leg(leg: dict, fx: Fixtures, out: str, name: str,
               prior_bytes: bytes | None) -> list[str]:
    """The chaos invariants for one completed leg (package docstring)."""
    v: list[str] = []
    if leg["quarantine"]:
        v.append(f"{name}: stray .quarantine sidecar (quarantine is off)")
    if leg["killed"]:
        # a SIGKILL may land at ANY instant — including after the atomic
        # commit (the journal outlives the rename so resume can survive a
        # commit-time crash, which widens exactly this window). The
        # destination must then be absent, the COMPLETE output, or the
        # intact previous file; torn bytes are the violation.
        if leg["out_exists"]:
            data = open(out, "rb").read()
            if normalize_output(data) != fx.reference_norm \
                    and (prior_bytes is None or data != prior_bytes):
                v.append(f"{name}: SIGKILL left TORN bytes at the "
                         "destination")
        return v
    if leg["rc"] == 0:
        if not leg["out_exists"]:
            v.append(f"{name}: success but no destination file")
        elif normalize_output(open(out, "rb").read()) != fx.reference_norm:
            v.append(f"{name}: success but bytes differ from the clean "
                     "reference")
        if leg["partial"] or leg["journal"]:
            v.append(f"{name}: success left stray .partial/.journal")
    else:
        if leg["out_exists"]:
            if prior_bytes is None:
                v.append(f"{name}: failure (rc={leg['rc']}) left bytes at "
                         "the destination")
            elif open(out, "rb").read() != prior_bytes:
                v.append(f"{name}: failure replaced the previous complete "
                         "destination with different bytes")
        if leg["partial"] != leg["journal"] and not out.endswith(".gz"):
            v.append(f"{name}: failure left an unpaired sidecar "
                     f"(partial={leg['partial']} journal={leg['journal']})")
    if leg["status"] is not None and leg["status"].get("leaked"):
        v.append(f"{name}: leaked threads {leg['status']['leaked']}")
    return v


def _remove_run_files(out: str, extra: tuple[str, ...] = ()) -> None:
    """Sweep one leg's output + sidecars, including every unique-suffix
    partial (``<out>.partial.<pid>-<hex>``, ISSUE 14) and — for pod
    legs — the rank/span segments, their journals/markers/leases,
    worker logs and the launcher state file (docs/scaleout.md)."""
    import glob

    from variantcalling_tpu.io import journal as journal_mod

    targets = [out, out + ".journal", out + ".quarantine",
               out + ".podrun.json", out + ".podrun.obs.jsonl"]
    targets += [out + s for s in extra]
    targets += journal_mod.list_partials(out)
    targets += glob.glob(glob.escape(out) + ".rank*")
    targets += glob.glob(glob.escape(out) + ".span*")
    for p in targets:
        try:
            os.remove(p)
        except OSError:
            pass


# ---------------------------------------------------------------------------
# the rank_kill pod fault class (docs/scaleout.md failure semantics)
# ---------------------------------------------------------------------------


def run_pod_leg(fx: Fixtures, out: str, layout: str, ranks: int,
                faults_spec: str = "", kill_rank: int | None = None,
                kill_after_chunks: int | None = None) -> dict:
    """One 2-rank local-launcher run (``tools/podrun`` as a subprocess),
    optionally SIGKILLing worker rank ``kill_rank`` once ITS segment
    journal shows ``kill_after_chunks`` committed chunks (the launcher's
    ``<out>.podrun.json`` state file maps rank -> worker pid)."""
    env = _child_env(layout, faults_spec)
    argv = [sys.executable, "-m", "tools.podrun", "--ranks", str(ranks),
            "--timeout", str(CHILD_TIMEOUT_S - 30), "--",
            "--input_file", fx.input_vcf, "--model_file", fx.model,
            "--model_name", "m", "--reference_file", fx.ref,
            "--output_file", out, "--backend", "cpu"]
    p = subprocess.Popen(argv, env=env, cwd=REPO,  # noqa: S603
                         stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                         text=True)
    killed = False
    if kill_rank is not None:
        jpath = f"{out}.rank{kill_rank}of{ranks}.seg.journal"
        spath = out + ".podrun.json"
        deadline = time.time() + CHILD_TIMEOUT_S
        while time.time() < deadline and p.poll() is None:
            try:
                with open(jpath, encoding="utf-8") as fh:
                    committed = max(0, len(fh.read().splitlines()) - 1)
            except OSError:
                committed = 0
            if committed >= kill_after_chunks:
                try:
                    with open(spath, encoding="utf-8") as fh:
                        state = json.load(fh)
                    pid = next(w["pid"] for w in state["workers"]
                               if w["rank"] == kill_rank)
                    os.kill(pid, signal.SIGKILL)
                    killed = True
                except (OSError, ValueError, KeyError, StopIteration,
                        ProcessLookupError):
                    pass  # worker already gone: the pod completes clean
                break
            time.sleep(0.02)
    try:
        stdout, _ = p.communicate(timeout=CHILD_TIMEOUT_S)
    except subprocess.TimeoutExpired:
        p.kill()
        stdout, _ = p.communicate(timeout=30)
    segs = [f"{out}.rank{r}of{ranks}.seg" for r in range(ranks)]
    return {"rc": p.returncode, "killed": killed,
            "out_exists": os.path.exists(out),
            "stdout": (stdout or "")[-4000:],
            "segments": [os.path.exists(s) for s in segs]}


def _check_pod_leg(leg: dict, fx: Fixtures, out: str, name: str) -> list[str]:
    """Pod invariants: a clean pod commits the clean-reference bytes and
    sweeps its segments; a rank-killed pod exits the launcher's DISTINCT
    code (3) with the destination untouched (surviving ranks' segments
    stay staged for the relaunch)."""
    v: list[str] = []
    if leg["killed"] and leg["rc"] != 0:
        if leg["rc"] != 3:
            v.append(f"{name}: podrun exited rc={leg['rc']} after a rank "
                     "SIGKILL (expected the distinct rank-kill code 3)")
        if leg["out_exists"]:
            data = open(out, "rb").read()
            if normalize_output(data) != fx.reference_norm:
                v.append(f"{name}: rank SIGKILL left bytes at the "
                         "destination that are not a complete output")
        return v
    # no kill landed (or it raced the worker's clean exit): the pod must
    # have completed byte-identically and swept its segments
    if leg["rc"] != 0:
        v.append(f"{name}: pod run failed rc={leg['rc']}: "
                 f"{leg['stdout'][-500:]}")
        return v
    if not leg["out_exists"]:
        v.append(f"{name}: pod success but no destination file")
    elif normalize_output(open(out, "rb").read()) != fx.reference_norm:
        v.append(f"{name}: pod success but bytes differ from the clean "
                 "reference")
    if any(leg["segments"]):
        v.append(f"{name}: pod success left staged rank segments behind")
    return v


def run_rank_kill_schedule(sched: Schedule, fx: Fixtures,
                           workdir: str) -> dict:
    """The rank_kill fault class end to end: a pod leg with one worker
    rank SIGKILLed mid-run, then a fault-free RELAUNCH that must resume
    from the per-rank journals/markers and commit byte-identically."""
    rk = sched.rank_kill or {}
    ranks = int(rk.get("ranks", 2))
    out = os.path.join(workdir, f"seed{sched.seed}_pod.vcf")
    _remove_run_files(out)
    legs: list[dict] = []
    violations: list[str] = []
    leg1 = run_pod_leg(fx, out, sched.layout, ranks,
                       faults_spec=sched.faults_env(),
                       kill_rank=int(rk.get("kill_rank", 1)),
                       kill_after_chunks=int(rk.get("after_chunks", 1)))
    legs.append(dict(leg1, name="fresh"))
    violations += _check_pod_leg(leg1, fx, out, "fresh")
    if leg1["killed"] and leg1["rc"] != 0:
        # the relaunch: no faults, no kill — per-rank journal resume +
        # marker skip must complete byte-identically
        leg2 = run_pod_leg(fx, out, sched.layout, ranks)
        legs.append(dict(leg2, name="relaunch"))
        violations += _check_pod_leg(leg2, fx, out, "relaunch")
    _remove_run_files(out, (".obs.jsonl",))
    return {"schedule": sched.to_json(), "describe": sched.describe(),
            "legs": [{k: leg[k] for k in ("name", "rc", "killed",
                                          "out_exists")}
                     for leg in legs],
            "violations": violations}


# ---------------------------------------------------------------------------
# the elastic pod fault classes (docs/scaleout.md "Elastic membership")
# ---------------------------------------------------------------------------


def run_elastic_leg(fx: Fixtures, out: str, layout: str, ranks: int,
                    faults_spec: str = "", chaos: str | None = None,
                    flap_kills: int = 0, after_chunks: int = 1) -> dict:
    """One ``tools/podrun --elastic`` run. ``flap_kills`` > 0 SIGKILLs
    that many span workers — each only once ITS journal shows
    ``after_chunks`` committed chunks (the state file maps spans ->
    pids) — exercising the re-cut + re-assignment path WITHIN the
    launch. Children pin ``VCTPU_THREADS=2``: span workers ride the
    streaming executor (like the cache schedules)."""
    env = _child_env(layout, faults_spec, {"VCTPU_THREADS": "2"})
    argv = [sys.executable, "-m", "tools.podrun", "--elastic",
            "--ranks", str(ranks), "--grace", "0.5",
            "--timeout", str(CHILD_TIMEOUT_S - 30)]
    if chaos is not None:
        argv += ["--chaos", chaos]
    argv += ["--", "--input_file", fx.input_vcf, "--model_file", fx.model,
             "--model_name", "m", "--reference_file", fx.ref,
             "--output_file", out, "--backend", "cpu"]
    p = subprocess.Popen(argv, env=env, cwd=REPO,  # noqa: S603
                         stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                         text=True)
    kills = 0
    if flap_kills > 0:
        spath = out + ".podrun.json"
        downed: set[int] = set()
        deadline = time.time() + CHILD_TIMEOUT_S
        while kills < flap_kills and time.time() < deadline \
                and p.poll() is None:
            try:
                with open(spath, encoding="utf-8") as fh:
                    workers = json.load(fh).get("workers") or []
            except (OSError, ValueError):
                workers = []
            for w in workers:
                pid = w.get("pid")
                if not pid or pid in downed:
                    continue
                lo, hi = w["span"]
                try:
                    with open(f"{out}.span{lo}-{hi}.seg.journal",
                              encoding="utf-8") as fh:
                        committed = max(0,
                                        len(fh.read().splitlines()) - 1)
                except OSError:
                    committed = 0
                if committed < after_chunks:
                    continue
                try:
                    os.kill(pid, signal.SIGKILL)
                except ProcessLookupError:
                    continue
                downed.add(pid)
                kills += 1
                break
            time.sleep(0.02)
    try:
        stdout, _ = p.communicate(timeout=CHILD_TIMEOUT_S)
    except subprocess.TimeoutExpired:
        p.kill()
        stdout, _ = p.communicate(timeout=30)
    import glob

    leftovers = sorted(os.path.basename(q) for q in
                       glob.glob(glob.escape(out) + ".span*")
                       if not q.endswith(".obs.jsonl"))
    return {"rc": p.returncode, "kills": kills,
            "out_exists": os.path.exists(out),
            "stdout": (stdout or "")[-4000:], "leftovers": leftovers}


#: an elastic pod failure must be one of the launcher's DISTINCT codes —
#: config (2), merge (4), timeout (5), span-gave-up (7) — never a hang
#: and never an undocumented code
ELASTIC_FAIL_CODES = (2, 4, 5, 7)


def _check_elastic_leg(leg: dict, fx: Fixtures, out: str,
                       name: str) -> list[str]:
    """Elastic pod invariants: success commits bytes identical to the
    clean reference (modulo normalize_output) and sweeps every span
    file; failure uses a distinct exit code with the destination
    untouched. Either way the launcher RETURNED — the
    hung-forever outcome is impossible by construction."""
    v: list[str] = []
    if leg["rc"] == 0:
        if not leg["out_exists"]:
            v.append(f"{name}: elastic success but no destination file")
        elif normalize_output(open(out, "rb").read()) != fx.reference_norm:
            v.append(f"{name}: elastic success but bytes differ from the "
                     "clean reference")
        if leg["leftovers"]:
            v.append(f"{name}: elastic success left span files behind: "
                     f"{leg['leftovers'][:4]}")
        return v
    if leg["rc"] not in ELASTIC_FAIL_CODES:
        v.append(f"{name}: elastic pod failed with UNDOCUMENTED code "
                 f"rc={leg['rc']} (expected one of "
                 f"{ELASTIC_FAIL_CODES}): {leg['stdout'][-400:]}")
    if leg["out_exists"]:
        v.append(f"{name}: elastic failure (rc={leg['rc']}) left bytes at "
                 "the destination")
    return v


def run_elastic_schedule(sched: Schedule, fx: Fixtures,
                         workdir: str) -> dict:
    """The elastic fault classes end to end — one leg each:

    - ``rank_flap``: SIGKILL k span workers mid-journal; the SAME launch
      must re-cut, adopt the journaled prefixes and commit
      byte-identically (no relaunch — that is the class's whole point);
    - ``steal_race``: the launcher spawns a duplicate claimant for a
      live (span, generation); the lease must yield one winner
      (``claim_lost`` reported) and the bytes stay identical;
    - ``join_during_merge``: a late join against a completed span must
      be refused by the persisted lease (``join_refused`` reported).
    """
    el = sched.elastic or {}
    mode = el.get("mode", "rank_flap")
    ranks = int(el.get("ranks", 2))
    out = os.path.join(workdir, f"seed{sched.seed}_elastic.vcf")
    _remove_run_files(out)
    violations: list[str] = []
    if mode == "rank_flap":
        leg = run_elastic_leg(fx, out, sched.layout, ranks,
                              faults_spec=sched.faults_env(),
                              flap_kills=int(el.get("kills", 1)),
                              after_chunks=int(el.get("after_chunks", 1)))
        violations += _check_elastic_leg(leg, fx, out, "flap")
        # the class only proves self-healing when a kill actually
        # landed; a worker outracing the killer is a (logged) miss,
        # not a product violation
        if leg["kills"] > 0 and leg["rc"] == 0 \
                and "membership: recut" not in leg["stdout"] \
                and "membership: reassign" not in leg["stdout"]:
            violations.append("flap: a worker was SIGKILLed but the "
                              "coordinator recorded no recut/reassign "
                              "transition")
    else:
        leg = run_elastic_leg(fx, out, sched.layout, ranks, chaos=mode)
        violations += _check_elastic_leg(leg, fx, out, mode)
        marker = ("claim_lost" if mode == "steal_race"
                  else "join_refused")
        if leg["rc"] == 0 and marker not in leg["stdout"]:
            violations.append(f"{mode}: the chaos drill completed "
                              f"without reporting {marker}")
    legs = [dict(leg, name=mode)]
    _remove_run_files(out, (".obs.jsonl",))
    return {"schedule": sched.to_json(), "describe": sched.describe(),
            "legs": [{k: leg[k] for k in ("name", "rc", "kills",
                                          "out_exists")}
                     for leg in legs],
            "violations": violations}


def run_cache_schedule(sched: Schedule, fx: Fixtures, workdir: str) -> dict:
    """The chunk-cache fault classes (docs/caching.md): the cache may
    only ever DEGRADE a run to cold — wrong bytes are the violation.

    - ``cache_poison``: a cold leg populates a fresh store, every
      entry's body gets one bit flipped, then a warm leg must detect the
      corruption (CRC), recompute, and still produce the reference
      bytes.
    - ``cache_torn``: a leg is SIGKILLed inside an entry write (the
      ``cache.entry_write`` hang window), then a fault-free leg over the
      same store must complete byte-identically — a torn tmp file can
      never be served.

    Children pin ``VCTPU_THREADS=2``: the cache rides the streaming
    executor, which degrades to the (cache-less) serial path on a
    single-core host — the schedule must exercise the store either way.
    """
    import shutil

    mode = sched.cache["mode"]
    out = os.path.join(workdir, f"seed{sched.seed}_cache.vcf")
    store = os.path.join(workdir, f"seed{sched.seed}_cache_store")
    shutil.rmtree(store, ignore_errors=True)
    _remove_run_files(out)
    cache_env = {"VCTPU_CACHE": "1", "VCTPU_CACHE_DIR": store,
                 "VCTPU_THREADS": "2"}
    legs: list[dict] = []
    violations: list[str] = []

    def check_clean(leg: dict, name: str) -> None:
        if leg["rc"] != 0:
            violations.append(f"{name}: leg failed rc={leg['rc']}"
                              + (f", {leg['status'].get('error')}"
                                 if leg["status"] else ""))
        else:
            violations.extend(_check_leg(leg, fx, out, name,
                                         prior_bytes=None))

    if mode == "poison":
        leg1 = run_leg(fx, out, sched.layout, sched.faults_env(), None,
                       extra_env=cache_env)
        legs.append(dict(leg1, name="populate"))
        check_clean(leg1, "populate")
        entries = [os.path.join(store, n) for n in
                   (os.listdir(store) if os.path.isdir(store) else [])
                   if n.endswith(".vcc")]
        if not violations and not entries:
            violations.append("populate: cold leg published no cache "
                              "entries (store never engaged)")
        for p in entries:
            with open(p, "r+b") as fh:
                data = bytearray(fh.read())
                data[len(data) // 2] ^= 0x01
                fh.seek(0)
                fh.write(bytes(data))
        leg2 = run_leg(fx, out, sched.layout, sched.faults_env(), None,
                       extra_env=cache_env)
        legs.append(dict(leg2, name="poisoned-warm"))
        check_clean(leg2, "poisoned-warm")
    else:  # torn: SIGKILL inside the first entry write
        spec = ",".join(filter(None, [sched.faults_env(),
                                      "cache.entry_write:1@30"]))
        leg1 = run_leg(fx, out, sched.layout, spec, 1, extra_env=cache_env)
        legs.append(dict(leg1, name="torn"))
        violations.extend(_check_leg(leg1, fx, out, "torn",
                                     prior_bytes=None))
        leg2 = run_leg(fx, out, sched.layout, "", None, extra_env=cache_env)
        legs.append(dict(leg2, name="recover"))
        check_clean(leg2, "recover")
    _remove_run_files(out, (".obs.jsonl",))
    shutil.rmtree(store, ignore_errors=True)
    return {"schedule": sched.to_json(), "describe": sched.describe(),
            "legs": [{k: leg[k] for k in
                      ("name", "rc", "killed", "partial", "journal")}
                     for leg in legs],
            "violations": violations}


def run_schedule(sched: Schedule, fx: Fixtures, workdir: str,
                 sabotage: str | None = None) -> dict:
    """One schedule end to end: the faulted fresh leg, then — whenever
    the faulted leg left a resumable journal (or was killed) — a
    fault-free RESUME leg that must complete byte-identically.
    ``rank_kill`` schedules route to the pod harness, ``cache``
    schedules to the chunk-cache harness, ``elastic`` schedules to the
    elastic-pod harness."""
    if sched.rank_kill is not None:
        return run_rank_kill_schedule(sched, fx, workdir)
    if sched.cache is not None:
        return run_cache_schedule(sched, fx, workdir)
    if sched.elastic is not None:
        return run_elastic_schedule(sched, fx, workdir)
    out = os.path.join(workdir, f"seed{sched.seed}.vcf")
    _remove_run_files(out)
    violations: list[str] = []
    legs: list[dict] = []
    leg1 = run_leg(fx, out, sched.layout, sched.faults_env(),
                   sched.kill_after_chunks, sabotage=sabotage)
    legs.append(dict(leg1, name="fresh"))
    violations += _check_leg(leg1, fx, out, "fresh", prior_bytes=None)
    if leg1["killed"] or leg1["rc"] != 0:
        # resume leg: same layout, no faults — the headline recovery
        # invariant (byte-identical completion after any interruption)
        leg2 = run_leg(fx, out, sched.layout, "", None, sabotage=sabotage)
        legs.append(dict(leg2, name="resume"))
        if leg2["rc"] != 0:
            violations.append(
                f"resume: rerun failed (rc={leg2['rc']}"
                + (f", {leg2['status'].get('error')}" if leg2["status"]
                   else "") + ")")
        else:
            violations += _check_leg(leg2, fx, out, "resume",
                                     prior_bytes=None)
    _remove_run_files(out, (".obs.jsonl",))
    return {"schedule": sched.to_json(), "describe": sched.describe(),
            "legs": [{k: leg[k] for k in
                      ("name", "rc", "killed", "partial", "journal")}
                     for leg in legs],
            "violations": violations}


# ---------------------------------------------------------------------------
# delta-shrink
# ---------------------------------------------------------------------------


def _simplifications(sched: Schedule):
    """Candidate one-step simplifications, most aggressive first."""
    if sched.rank_kill is not None:
        # does the violation need the pod at all? dropping rank_kill
        # degrades the schedule to the ordinary single-process flow
        yield dataclasses.replace(sched, rank_kill=None)
    if sched.cache is not None:
        # does the violation need the cache? dropping it degrades the
        # schedule to the ordinary (cache-off) single-process flow
        yield dataclasses.replace(sched, cache=None)
    if sched.elastic is not None:
        # does the violation need the elastic pod at all?
        yield dataclasses.replace(sched, elastic=None)
        if sched.elastic.get("kills", 0) > 1:
            yield dataclasses.replace(
                sched, elastic=dict(sched.elastic, kills=1))
    if sched.kill_after_chunks is not None:
        yield dataclasses.replace(sched, kill_after_chunks=None)
    for i in range(len(sched.faults)):
        yield dataclasses.replace(
            sched, faults=sched.faults[:i] + sched.faults[i + 1:])
    for i, f in enumerate(sched.faults):
        if f.times is None or f.times > 1:
            g = dataclasses.replace(f, times=1)
            yield dataclasses.replace(
                sched, faults=sched.faults[:i] + [g] + sched.faults[i + 1:])
        if f.after:
            g = dataclasses.replace(f, after=0)
            yield dataclasses.replace(
                sched, faults=sched.faults[:i] + [g] + sched.faults[i + 1:])
    if sched.layout != "serial":
        yield dataclasses.replace(sched, layout="serial")


def shrink_schedule(sched: Schedule, fx: Fixtures, workdir: str,
                    sabotage: str | None = None,
                    budget: int = 24) -> tuple[Schedule, dict]:
    """Greedy delta-shrink: keep applying any one-step simplification
    that still violates an invariant, until none does (or the evaluation
    budget is spent). Returns the minimal schedule + its failing result."""
    current = sched
    result = run_schedule(current, fx, workdir, sabotage=sabotage)
    spent = 1
    progress = True
    while progress and spent < budget:
        progress = False
        for cand in _simplifications(current):
            if spent >= budget:
                break
            r = run_schedule(cand, fx, workdir, sabotage=sabotage)
            spent += 1
            if r["violations"]:
                current, result = cand, r
                progress = True
                break
    return current, result


# ---------------------------------------------------------------------------
# campaign
# ---------------------------------------------------------------------------


def run_campaign(seeds: list[int], workdir: str | None = None,
                 records: int = 2000, sabotage: str | None = None,
                 shrink: bool = True, model_family: str = "forest",
                 log=print) -> dict:
    """Run one schedule per seed; on violations, delta-shrink the first
    failing schedule and write the minimal repro JSON next to the report.
    Returns the campaign report dict (see ``__main__`` for the exit-code
    mapping)."""
    t0 = time.time()
    owns_workdir = workdir is None
    workdir = workdir or tempfile.mkdtemp(prefix="chaoshunt-")
    os.makedirs(workdir, exist_ok=True)
    fx = build_fixtures(workdir, records=records,
                        model_family=model_family)
    results = []
    first_violation: dict | None = None
    for seed in seeds:
        sched = draw_schedule(seed)
        r = run_schedule(sched, fx, workdir, sabotage=sabotage)
        results.append(r)
        flag = "VIOLATION" if r["violations"] else "ok"
        log(f"chaoshunt seed {seed:>4} [{sched.describe()}] -> {flag}")
        for msg in r["violations"]:
            log(f"  ! {msg}")
        if r["violations"] and first_violation is None:
            first_violation = r
    repro_path = None
    shrunk = None
    if first_violation is not None and shrink:
        log("chaoshunt: delta-shrinking the first violating schedule ...")
        minimal, minimal_result = shrink_schedule(
            Schedule.from_json(first_violation["schedule"]), fx, workdir,
            sabotage=sabotage)
        shrunk = {"schedule": minimal.to_json(),
                  "describe": minimal.describe(),
                  "violations": minimal_result["violations"]}
        repro_path = os.path.join(workdir, "chaoshunt_repro.json")
        with open(repro_path, "w", encoding="utf-8") as fh:
            json.dump({"schedule": minimal.to_json(),
                       "violations": minimal_result["violations"],
                       "records": records,
                       "model_family": model_family},
                      fh, indent=2, sort_keys=True)
            fh.write("\n")
        log(f"chaoshunt: minimal repro [{minimal.describe()}] "
            f"written to {repro_path}")
    n_viol = sum(1 for r in results if r["violations"])
    report = {
        "seeds": len(seeds),
        "violating_schedules": n_viol,
        "schedules": results,
        "shrunk": shrunk,
        "repro": repro_path,
        "workdir": workdir if (n_viol or not owns_workdir) else None,
        "wall_s": round(time.time() - t0, 1),
    }
    if owns_workdir and not n_viol:
        import shutil

        shutil.rmtree(workdir, ignore_errors=True)
    return report


def replay(repro_path: str, workdir: str | None = None,
           log=print) -> dict:
    """Replay one shrunk repro JSON (the campaign's output artifact)."""
    with open(repro_path, encoding="utf-8") as fh:
        repro = json.load(fh)
    sched = Schedule.from_json(repro["schedule"])
    owns = workdir is None
    workdir = workdir or tempfile.mkdtemp(prefix="chaoshunt-replay-")
    fx = build_fixtures(workdir, records=int(repro.get("records", 2000)),
                        model_family=repro.get("model_family", "forest"))
    result = run_schedule(sched, fx, workdir)
    log(f"chaoshunt replay [{sched.describe()}] -> "
        + ("VIOLATION" if result["violations"] else "ok"))
    for msg in result["violations"]:
        log(f"  ! {msg}")
    if owns and not result["violations"]:
        import shutil

        shutil.rmtree(workdir, ignore_errors=True)
    return result
