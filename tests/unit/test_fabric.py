"""Serving fabric (ISSUE 20, docs/serving_fabric.md): the router tier
that composes ``vctpu serve`` × elastic spans into one front door.

Covers the transport layer (bearer tokens, per-principal quota,
chunked request/response), contig-aware span placement
(``rank_plan.contig_spans``), the fabric knobs contract, and the
in-process end-to-end fleet: a Router over two resident Backends must
answer a streamed filter request with bytes sha256-identical to the
batch CLI (seam merge on the response path), reject bad credentials
distinctly, re-span onto the survivor when a backend dies mid-fleet,
and fail with the DISTINCT ``backend_lost`` status — never hang —
when no live backend remains. The subprocess twin (real processes,
SIGKILL) is tests/system/test_fabric_fleet.py + the loadhunt
``backend_kill`` campaign."""

import hashlib
import json
import os
import pickle
import urllib.request

import numpy as np
import pytest

from tests.conftest import assert_no_stream_leaks
from variantcalling_tpu import knobs
from variantcalling_tpu.parallel import rank_plan
from variantcalling_tpu.serve import transport

#: directories the leak sentinel sweeps after every test in this module
_WATCHED_DIRS: list[str] = []


@pytest.fixture(autouse=True)
def _leak_sentinel():
    yield
    assert_no_stream_leaks(_WATCHED_DIRS)


def _strip_prov(data: bytes) -> bytes:
    from tools.chaoshunt.harness import normalize_output

    return normalize_output(data)


def _sha(data: bytes) -> str:
    return hashlib.sha256(_strip_prov(data)).hexdigest()


# ---------------------------------------------------------------------------
# transport: tokens, quota
# ---------------------------------------------------------------------------


def test_parse_tokens_roundtrip():
    assert transport.parse_tokens("") == {}
    assert transport.parse_tokens("t1:alice, t2:bob,") == \
        {"t1": "alice", "t2": "bob"}


@pytest.mark.parametrize("spec", ["t1", "t1:", ":alice", "t1:a,oops"])
def test_parse_tokens_malformed_refused(spec):
    with pytest.raises(ValueError, match="malformed"):
        transport.parse_tokens(spec)


def test_authenticate_empty_table_is_single_tenant():
    assert transport.authenticate(None, {}) == "anonymous"
    assert transport.authenticate("Bearer whatever", {}) == "anonymous"


def test_authenticate_bearer_table():
    tokens = {"sekrit": "alice"}
    assert transport.authenticate("Bearer sekrit", tokens) == "alice"
    for bad in (None, "", "Basic sekrit", "Bearer nope"):
        with pytest.raises(transport.AuthError):
            transport.authenticate(bad, tokens)


def test_principal_quota_caps_per_principal():
    q = transport.PrincipalQuota(limit=2)
    r1 = q.acquire("alice")
    r2 = q.acquire("alice")
    with pytest.raises(transport.QuotaError):
        q.acquire("alice")
    # independent principals do not share the cap
    rb = q.acquire("bob")
    assert q.in_flight() == {"alice": 2, "bob": 1}
    r1()
    r1()  # idempotent release must not double-free the slot
    assert q.in_flight()["alice"] == 1
    q.acquire("alice")
    r2()
    rb()


# ---------------------------------------------------------------------------
# contig-aware span placement
# ---------------------------------------------------------------------------


def test_contig_spans_tile_record_region(fabric_world):
    path = fabric_world["input"]
    from variantcalling_tpu.io import vcf as vcf_mod

    header_end, total = vcf_mod.scan_record_region(path)
    for n in (1, 2, 3):
        spans = rank_plan.contig_spans(path, n)
        # exact tiling of the record region, whatever the snaps did
        assert spans[0][0] == header_end
        assert spans[-1][1] == total
        for (_, hi), (lo2, _) in zip(spans, spans[1:]):
            assert hi == lo2
        # every cut lands on a record (line) start
        with open(path, "rb") as fh:
            for lo, _ in spans[1:]:
                fh.seek(lo - 1)
                assert fh.read(1) == b"\n"


def test_contig_spans_prefer_contig_boundaries(tmp_path):
    # 2 contigs with identical record sizes, split 44/36: the byte
    # midpoint lands 4 records BEFORE the contig boundary, within the
    # 20% slack budget — the snap must advance the cut so each contig
    # lands whole on one span (reference-locality placement)
    path = str(tmp_path / "two_contigs.vcf")
    with open(path, "wb") as fh:
        fh.write(b"##fileformat=VCFv4.2\n#CHROM\tPOS\n")
        for contig, count in ((b"chr1", 44), (b"chr2", 36)):
            for i in range(count):
                fh.write(contig + b"\t%06d\tA\tT\n" % (i + 1))
    spans = rank_plan.contig_spans(path, 2)
    assert len(spans) == 2
    with open(path, "rb") as fh:
        fh.seek(spans[1][0])
        assert fh.read(4) == b"chr2"


# ---------------------------------------------------------------------------
# knobs contract
# ---------------------------------------------------------------------------


def test_fabric_knobs_registered_and_unscopable():
    names = ["VCTPU_FABRIC_BACKENDS", "VCTPU_FABRIC_HEARTBEAT_S",
             "VCTPU_FABRIC_DEAD_AFTER", "VCTPU_FABRIC_QUOTA",
             "VCTPU_FABRIC_TOKENS", "VCTPU_FABRIC_STREAM_CHUNK_BYTES",
             "VCTPU_FABRIC_SPAN_ATTEMPTS"]
    from variantcalling_tpu.serve import daemon

    for name in names:
        assert name in knobs.REGISTRY, name
        # fabric topology must not be settable per request: the daemon's
        # isolation envelope refuses these with a per-request 400
        assert name in daemon._UNSCOPABLE, name
    contract = json.load(open(os.path.join(
        os.path.dirname(knobs.__file__), "..", "tools", "vctpu_lint",
        "knobs_contract.json")))["knobs"]
    for name in names:
        assert contract[name]["class"] == "byte_neutral", name


# ---------------------------------------------------------------------------
# end-to-end: in-process fleet
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def fabric_world(tmp_path_factory):
    from variantcalling_tpu.pipelines.filter_variants import run as frun
    from variantcalling_tpu.synthetic import make_fixtures, synthetic_forest

    d = tmp_path_factory.mktemp("fabric_world")
    _WATCHED_DIRS.append(str(d))
    make_fixtures(str(d), n=1500, genome_len=120_000)
    model = synthetic_forest(np.random.default_rng(0), n_trees=8, depth=4)
    model_pkl = str(d / "model.pkl")
    with open(model_pkl, "wb") as fh:
        pickle.dump({"m": model}, fh)
    ref_out = str(d / "reference.vcf")
    assert frun(["--input_file", str(d / "calls.vcf"),
                 "--model_file", model_pkl, "--model_name", "m",
                 "--reference_file", str(d / "ref.fa"),
                 "--output_file", ref_out, "--backend", "cpu"]) == 0
    return {"dir": str(d), "input": str(d / "calls.vcf"),
            "model": model_pkl, "ref": str(d / "ref.fa"),
            "reference_bytes": open(ref_out, "rb").read()}


def _params(w, out_name, **extra):
    return {"model": w["model"], "model_name": "m",
            "reference": w["ref"], "output_name": out_name,
            "deadline_s": 120.0, **extra}


def _boot_fleet(n_backends=2, router_backends=None):
    from variantcalling_tpu.serve.backend import Backend
    from variantcalling_tpu.serve.router import Router

    backends = []
    for _ in range(n_backends):
        b = Backend(port=0)
        b.start()
        backends.append(b)
    router = Router(port=0, backends=router_backends
                    or [b.address for b in backends])
    router.start()
    return router, backends


def test_fabric_parity_auth_and_observability(fabric_world, tmp_path,
                                              monkeypatch):
    w = fabric_world
    ref_sha = _sha(w["reference_bytes"])
    router, backends = _boot_fleet()
    try:
        # -- the headline: a streamed 2-span request reproduces the
        #    batch CLI's bytes, sha256-asserted ---------------------------
        out2 = str(tmp_path / "fanout.vcf")
        code, stats = transport.client_filter(
            router.address, _params(w, "fanout.vcf", ranks=2),
            w["input"], out2)
        assert code == 200, stats
        assert stats["spans"] == 2
        assert _sha(open(out2, "rb").read()) == ref_sha
        # -- ranks=1 rides the same path and merges one span -------------
        out1 = str(tmp_path / "single.vcf")
        code, stats = transport.client_filter(
            router.address, _params(w, "single.vcf", ranks=1),
            w["input"], out1)
        assert code == 200, stats
        assert stats["spans"] == 1
        assert _sha(open(out1, "rb").read()) == ref_sha
        # -- missing required params are a distinct 400 ------------------
        code, payload = transport.client_filter(
            router.address, {"output_name": "x.vcf"}, w["input"],
            str(tmp_path / "x.vcf"))
        assert code == 400 and payload["status"] == "bad_request"
        # -- fleet status + prom export ----------------------------------
        with urllib.request.urlopen(router.address + "/v1/status",
                                    timeout=10) as resp:
            status = json.loads(resp.read())
        assert status["role"] == "router"
        assert status["fleet"]["alive"] == 2
        with urllib.request.urlopen(router.address + "/v1/fabric/backends",
                                    timeout=10) as resp:
            reg = json.loads(resp.read())
        assert [b["alive"] for b in reg["backends"]] == [True, True]
        # the heartbeat cargo: each backend's rolling-SLO series rides
        # the registry (distributed admission reads these)
        assert all("endpoints" in b["status"] for b in reg["backends"])
        with urllib.request.urlopen(router.address + "/v1/metrics",
                                    timeout=10) as resp:
            prom = resp.read().decode()
        assert 'endpoint="filter"' in prom
        # -- bearer auth at the front door (fresh router, same fleet) ----
        monkeypatch.setenv("VCTPU_FABRIC_TOKENS", "sekrit:alice")
        from variantcalling_tpu.serve.router import Router

        auth_router = Router(port=0,
                             backends=[b.address for b in backends])
        auth_router.start()
        try:
            code, payload = transport.client_filter(
                auth_router.address, _params(w, "a.vcf", ranks=2),
                w["input"], str(tmp_path / "a.vcf"))
            assert code == 401 and payload["status"] == "unauthorized"
            code, payload = transport.client_filter(
                auth_router.address, _params(w, "a.vcf", ranks=2),
                w["input"], str(tmp_path / "a.vcf"), token="wrong")
            assert code == 401, payload
            out_auth = str(tmp_path / "authed.vcf")
            code, stats = transport.client_filter(
                auth_router.address, _params(w, "authed.vcf", ranks=2),
                w["input"], out_auth, token="sekrit")
            assert code == 200, stats
            assert _sha(open(out_auth, "rb").read()) == ref_sha
        finally:
            auth_router.drain("test")
    finally:
        router.drain("test")
        for b in backends:
            b.drain("test")


def test_fabric_respan_on_death_then_distinct_backend_lost(
        fabric_world, tmp_path, monkeypatch):
    w = fabric_world
    ref_sha = _sha(w["reference_bytes"])
    # a long heartbeat freezes the registry between beats, so the DEATH
    # is discovered by the span attempt itself (the re-span path), not
    # raced away by the poller
    monkeypatch.setenv("VCTPU_FABRIC_HEARTBEAT_S", "60")
    router, (b1, b2) = _boot_fleet()
    try:
        # warm both backends through the front door
        code, _ = transport.client_filter(
            router.address, _params(w, "warm.vcf", ranks=2),
            w["input"], str(tmp_path / "warm.vcf"))
        assert code == 200
        # kill b1 (the lowest-id backend — the placement preference, so
        # at least one span is guaranteed to attempt the corpse)
        b1.drain("test")
        out = str(tmp_path / "respan.vcf")
        code, stats = transport.client_filter(
            router.address, _params(w, "respan.vcf", ranks=2),
            w["input"], out)
        assert code == 200, stats
        assert stats["respans"] >= 1
        assert _sha(open(out, "rb").read()) == ref_sha
        # now the survivor dies too: the next request must fail with the
        # DISTINCT backend_lost status, bounded — never hang
        b2.drain("test")
        code, payload = transport.client_filter(
            router.address, _params(w, "lost.vcf", ranks=2),
            w["input"], str(tmp_path / "lost.vcf"))
        assert code in (502, 503), payload
        assert payload["status"] in ("backend_lost", "shed")
        assert not os.path.exists(str(tmp_path / "lost.vcf"))
    finally:
        router.drain("test")
