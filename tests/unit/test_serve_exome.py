"""The exome daemon as the benchmark's ``forest-t40d6-hg38x2-exome``
configuration states it (ISSUE 30), on the CPU at a small size: one resident
``vctpu serve`` daemon on the jit engine, warmed through ``/v1/warm``, holds
two reference genomes on the device and serves concurrent clients.

- (a) four concurrent clients alternating two references: every output equals
  the cold CLI's bytes (which took the OTHER program layout, the host window
  gather) and agrees with the benchmark's plain reference
  (``benchmarks/reference.py``) within the configuration's limits;
- (b) ``/v1/warm`` with a reference is what makes a small request take the
  ``genome_resident=True`` program, and ``serve.requests_genome_resident``
  counts it;
- (c) two requests in flight under the daemon's one obs run each leave their
  own ``profile/pipeline`` row, a full set of ``profile/stage`` rows and
  spans that carry their ``req`` under a ``serve_request`` root;
- (d) a planted fault (one tree's leaf values moved) makes (a)'s comparison fail.
"""

from __future__ import annotations

import json
import os
import threading

import pytest

from tests.conftest import assert_no_stream_leaks

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks")
GENOME_LEN, N_VARIANTS, SEED = 240_000, 480, 11
#: the rows one pipeline run's profile holds (pooled layout, one chunk)
STAGE_FAMILIES = {"ingest", "parse", "score_stage", "host_featurize",
                  "prepare_inputs", "fused_program", "dispatch_feed",
                  "dispatch_enqueue", "dispatch_wait", "score_finalize",
                  "render_stage", "writeback",
                  "stream_open", "stream_close", "commit", "journal_append"}
_WATCHED: list[str] = []


@pytest.fixture(autouse=True)
def _leak_sentinel():
    yield
    assert_no_stream_leaks(_WATCHED)


@pytest.fixture(scope="module")
def world(tmp_path_factory):
    """Two small references, a callset for each, the configuration's forest
    from its ``weights_seed``, and the cold CLI's output per reference, all
    under the jit engine with no genome on the device."""
    from variantcalling_tpu import engine as engine_mod
    from variantcalling_tpu import featurize

    mp = pytest.MonkeyPatch()
    mp.syspath_prepend(BENCH)
    mp.setenv("VCTPU_ENGINE", "jit")
    saved = engine_mod._RESOLVED
    engine_mod.reset_for_tests()
    featurize._DEVICE_GENOME_CACHE.clear()
    import fixtures
    import lookup

    from variantcalling_tpu.models import registry
    from variantcalling_tpu.pipelines.filter_variants import run as cli

    with open(os.path.join(BENCH, "configs", "forest-t40d6-hg38x2-exome.json"),
              encoding="utf-8") as fh:
        config = json.load(fh)
    d = str(tmp_path_factory.mktemp("serve_exome"))
    _WATCHED.append(d)
    family = lookup.load("families", config["family"])
    weights = family.arrays(config["weights_seed"], config)
    w = {"dir": d, "config": config, "weights": weights, "family": family,
         "models": os.path.join(d, "models.pkl"), "refs": []}
    registry.save_models(w["models"], {config["model_name"]:
                                       family.to_program(config, weights)})
    for r in config["references"]:
        seed, nc = r["reference_seed"], r["n_contigs"]
        ref = {"seed": seed, "n_contigs": nc,
               "path": os.path.join(d, f"ref_{seed}.fa"),
               "calls": os.path.join(d, f"calls_{seed}.vcf"),
               "cold": os.path.join(d, f"cold_{seed}.vcf")}
        fixtures.write_reference(ref["path"], seed, GENOME_LEN, nc)
        fixtures.write_callset(ref["calls"], seed, GENOME_LEN, nc, N_VARIANTS, SEED)
        assert cli(["--input_file", ref["calls"], "--model_file", w["models"],
                    "--model_name", config["model_name"], "--reference_file",
                    ref["path"], "--output_file", ref["cold"]]) == 0
        w["refs"].append(ref)
    # the cold CLI gathered its windows on the host: nothing went to the device
    assert featurize.device_genome_stats()["entries"] == 0
    yield w
    featurize._DEVICE_GENOME_CACHE.clear()
    engine_mod._RESOLVED = saved
    mp.undo()


def start_daemon(w, name: str, obs_log: str | None = None):
    from variantcalling_tpu.serve.daemon import Server

    server = Server(socket_path=os.path.join(w["dir"], f"{name}.sock"),
                    obs_log=obs_log)
    server.start()
    return server


def post(server, path: str, body: dict) -> tuple[int, dict]:
    from variantcalling_tpu.serve import transport

    with transport.request(server.address, "POST", path,
                           headers={"Content-Type": "application/json"},
                           body=json.dumps(body).encode(), timeout=300) as r:
        return r.status, r.json()


def filter_body(w, ref: dict, out: str, models: str | None = None, **extra) -> dict:
    return {"input": ref["calls"], "model": models or w["models"],
            "model_name": w["config"]["model_name"], "reference": ref["path"],
            "output": out, **extra}


def warm(server, w, models: str | None = None) -> dict:
    for ref in w["refs"]:
        code, payload = post(server, "/v1/warm", {
            "model": models or w["models"],
            "model_name": w["config"]["model_name"], "reference": ref["path"]})
        assert code == 200 and payload["status"] == "ok", payload
    return payload


def four_clients(server, w, tag: str, models: str | None = None) -> list[dict]:
    """Four closed-loop clients, three requests each, alternating the
    references as the cell's traffic does; one row per request."""
    rows, lock = [], threading.Lock()

    def client(c: int) -> None:
        for k in range(3):
            ref = w["refs"][(c + k) % len(w["refs"])]
            out = os.path.join(w["dir"], f"{tag}_c{c}r{k}.vcf")
            code, payload = post(server, "/v1/filter",
                                 filter_body(w, ref, out, models=models))
            with lock:
                rows.append({"ref": ref, "out": out, "code": code,
                             "status": payload.get("status")})

    threads = [threading.Thread(target=client, args=(c,)) for c in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    assert not any(t.is_alive() for t in threads)
    return rows


def compare_with_reference(w, ref: dict, out: str, weights: dict) -> dict:
    import fixtures
    import reference
    import run_cell

    with open(ref["calls"], "rb") as fh:
        lines_in = run_cell.body_lines(fh.read())
    with open(out, "rb") as fh:
        lines_out = run_cell.body_lines(fh.read())
    body = fixtures.contig_body(ref["seed"], GENOME_LEN // ref["n_contigs"])
    return reference.compare(lines_in, lines_out, family=w["config"]["family"],
                             weights=weights, body=body, n_contigs=ref["n_contigs"],
                             score_limit=w["config"]["limits"]["score_gap_max"])


def within_limits(got: dict, limits: dict) -> bool:
    return all(got[k] <= limits[k] for k in limits)


# -- (a) ----------------------------------------------------------------------

@pytest.fixture(scope="module")
def served(world):
    server = start_daemon(world, "a")
    try:
        resident = warm(server, world)["resident"]
        rows = four_clients(server, world, "a")
        status = server.status_payload()
    finally:
        server.drain("test")
    yield {"rows": rows, "resident": resident, "status": status}
    for r in rows:
        if os.path.exists(r["out"]):
            os.remove(r["out"])


def test_warm_brings_both_genomes_to_the_device(served, world):
    dev = served["resident"]["device_genomes"]
    assert dev["entries"] == 2 and dev["bytes"] >= 2 * GENOME_LEN
    assert served["status"]["resident"]["device_genomes"] == dev
    assert all(r["code"] == 200 and r["status"] == "ok" for r in served["rows"])
    assert len(served["rows"]) == 12


@pytest.mark.parametrize("which", [0, 1])
def test_every_output_equals_the_cold_cli(served, world, which):
    ref = world["refs"][which]
    with open(ref["cold"], "rb") as fh:
        cold = fh.read()
    outs = [r["out"] for r in served["rows"] if r["ref"] is ref]
    assert len(outs) == 6
    for out in outs:
        with open(out, "rb") as fh:
            assert fh.read() == cold, out


@pytest.mark.parametrize("which", [0, 1])
def test_every_output_agrees_with_the_plain_reference(served, world, which):
    ref = world["refs"][which]
    out = next(r["out"] for r in served["rows"] if r["ref"] is ref)
    got = compare_with_reference(world, ref, out, world["weights"])
    assert got["records"] == N_VARIANTS
    assert within_limits(got, world["config"]["limits"]), got


# -- (b) ----------------------------------------------------------------------

def test_warm_decides_residency_for_a_small_request(world):
    from variantcalling_tpu import featurize

    featurize._DEVICE_GENOME_CACHE.clear()
    ref = world["refs"][0]
    out = os.path.join(world["dir"], "b.vcf")
    server = start_daemon(world, "b")
    counter = server.metrics.registry.counter("serve.requests_genome_resident")
    try:
        # never warmed: 480 rows are under the upload's worth, the host gathers
        code, payload = post(server, "/v1/filter", filter_body(world, ref, out))
        assert code == 200 and payload["status"] == "ok"
        assert counter.value == 0
        assert server.status_payload()["resident"]["device_genomes"]["entries"] == 0
        code, payload = post(server, "/v1/warm", {"reference": ref["path"]})
        assert code == 200 and payload["warmed"] == ["reference", "device_genome"]
        assert payload["resident"]["device_genomes"]["entries"] == 1
        code, payload = post(server, "/v1/filter", filter_body(world, ref, out))
        assert code == 200 and payload["status"] == "ok"
        assert counter.value == 1
        # two layouts, one answer
        with open(out, "rb") as got, open(ref["cold"], "rb") as cold:
            assert got.read() == cold.read()
    finally:
        server.drain("test")
        os.remove(out)


# -- (c) ----------------------------------------------------------------------

@pytest.fixture(scope="module")
def two_in_flight(world):
    """Two requests that are both inside their pipelines at once: each one's
    first chunk body waits 0.6 s, and they start together."""
    log = os.path.join(world["dir"], "c.obs.jsonl")
    server = start_daemon(world, "c", obs_log=log)
    gate = threading.Barrier(2, timeout=60)
    reqs: dict[int, str] = {}

    def client(c: int) -> None:
        ref = world["refs"][c]
        gate.wait()
        code, payload = post(server, "/v1/filter", filter_body(
            world, ref, os.path.join(world["dir"], f"c{c}.vcf"),
            faults="pipeline.stage_hang:1@0.6"))
        if code == 200 and payload["status"] == "ok":
            reqs[c] = payload["req"]

    try:
        warm(server, world)
        threads = [threading.Thread(target=client, args=(c,)) for c in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
    finally:
        server.drain("test")
    assert len(reqs) == 2
    with open(log, encoding="utf-8") as fh:
        events = [json.loads(ln) for ln in fh if ln.strip()]
    for c in range(2):
        os.remove(os.path.join(world["dir"], f"c{c}.vcf"))
    os.remove(log)
    return {"events": events, "reqs": sorted(reqs.values())}


def of(events, req, kind, name=None):
    return [e for e in events if e.get("req") == req and e["kind"] == kind
            and name in (None, e["name"])]


def test_the_two_requests_overlapped(two_in_flight):
    a, b = ([e for e in of(two_in_flight["events"], r, "span", "serve_request")]
            for r in two_in_flight["reqs"])
    assert len(a) == len(b) == 1
    (a,), (b,) = a, b
    assert max(a["start"], b["start"]) < min(a["start"] + a["dur"],
                                             b["start"] + b["dur"])


@pytest.mark.parametrize("which", [0, 1])
def test_each_request_leaves_its_own_profile(two_in_flight, which):
    events, req = two_in_flight["events"], two_in_flight["reqs"][which]
    pipelines = of(events, req, "profile", "pipeline")
    assert len(pipelines) == 1 and pipelines[0]["records"] == N_VARIANTS
    rows = of(events, req, "profile", "stage")
    assert {r["stage"].split(".")[0] for r in rows} == STAGE_FAMILIES
    assert sorted(r["stage"] for r in rows) == sorted(pipelines[0]["stages"])
    # every row is complete: the whole callset passed each top-level stage
    top = [r for r in rows if "parent" not in r]
    assert {r["stage"].split(".")[0] for r in top} == {
        "ingest", "parse", "score_stage", "render_stage", "writeback"}
    for family in ("parse", "score_stage", "render_stage"):
        assert sum(r["records"] for r in top
                   if r["stage"].split(".")[0] == family) == N_VARIANTS
    # and no row of either request went astray
    assert not [e for e in events if e["kind"] == "profile"
                and e["name"] in ("stage", "pipeline") and "req" not in e]


@pytest.mark.parametrize("which", [0, 1])
def test_spans_carry_their_request_under_a_serve_request_root(two_in_flight, which):
    events, req = two_in_flight["events"], two_in_flight["reqs"][which]
    spans = of(events, req, "span")
    names = {s["name"] for s in spans}
    assert {"serve_request", "serve_admit", "serve_state", "serve_respond",
            "stream", "parse", "score_stage", "dispatch_wait", "render_stage",
            "writeback"} <= names
    parent_of = {}
    for s in spans:
        if s["name"] != "serve_request":
            assert "parent" in s, s
            parent_of.setdefault(s["name"], set()).add(s["parent"])
    for name in parent_of:
        seen, at = set(), {name}
        while at and "serve_request" not in at:
            seen |= at
            at = {p for n in at for p in parent_of.get(n, ())} - seen
        assert "serve_request" in at, (name, parent_of[name])
    root, = of(events, req, "span", "serve_request")
    assert "parent" not in root and root["status"] == "ok" and root["code"] == 200
    admit, = of(events, req, "span", "serve_admit")
    assert admit["queued"] is False  # two slots, two requests
    for s in spans:  # and the root covers them all
        assert root["start"] <= s["start"] + 1e-6
        assert s["start"] + s["dur"] <= root["start"] + root["dur"] + 1e-3


# -- (d) ----------------------------------------------------------------------

def test_a_planted_fault_is_not_correct(world):
    """The leaf values of one tree of the forty moved by 0.2, so every score
    moves by 0.005: the daemon serves that model faithfully, and the
    comparison against the SOUND weights fails."""
    import numpy as np

    from variantcalling_tpu.models import registry

    config = world["config"]
    wrong = dict(world["weights"], value=np.array(world["weights"]["value"]))
    leaves = np.flatnonzero(world["weights"]["feature"][0] < 0)
    wrong["value"][0, leaves] += np.float32(0.2)
    models = os.path.join(world["dir"], "models_wrong.pkl")
    registry.save_models(models, {config["model_name"]:
                                  world["family"].to_program(config, wrong)})
    server = start_daemon(world, "d")
    try:
        warm(server, world, models=models)
        rows = four_clients(server, world, "d", models=models)
    finally:
        server.drain("test")
    try:
        assert all(r["code"] == 200 for r in rows)
        got = compare_with_reference(world, rows[0]["ref"], rows[0]["out"],
                                     world["weights"])
        assert not within_limits(got, config["limits"]), got
        assert got["score_gap_max"] > config["limits"]["score_gap_max"]
        # the same outputs against the weights that made them are correct
        assert within_limits(compare_with_reference(
            world, rows[0]["ref"], rows[0]["out"], wrong), config["limits"])
    finally:
        for r in rows:
            os.remove(r["out"])
        os.remove(models)
