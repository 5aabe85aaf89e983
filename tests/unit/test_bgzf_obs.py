"""What a ``.vcf.gz`` run tells obs (ISSUE 36): the ``inflate``,
``compress_stage`` and ``tabix_index`` spans, the ``bgzf.*`` and ``tabix.*``
counters, the index inside the pipeline's wall, and an index that cannot be
written made visible. A plain-text run emits none of the spans and reads 0.
Since ISSUE 37 the index is gathered in the pipeline: ``tabix_index`` rows on
the workers that render, one on the ordered threads, and two counters that say
whether a file's index came from there or from the second pass."""

from __future__ import annotations

import gzip
import json
import os
import pickle

import numpy as np
import pytest

from variantcalling_tpu.io import bgzf as bgzf_mod

native = pytest.importorskip("variantcalling_tpu.native")

N = 6000
SPANS = ("inflate", "compress_stage", "tabix_index")
COUNTERS = ("bgzf.in_bytes", "bgzf.in_blocks", "bgzf.inflate_shards",
            "bgzf.text_bytes_in", "bgzf.text_bytes_out", "bgzf.out_bytes",
            "bgzf.out_blocks", "tabix.records", "tabix.index_skipped",
            "tabix.index_streamed", "tabix.index_second_pass",
            "bgzf.deflate_members", "bgzf.libdeflate_members")


@pytest.fixture(autouse=True)
def _engine_cache_isolated():
    yield
    from variantcalling_tpu import engine as engine_mod

    engine_mod.reset_for_tests()


@pytest.fixture(scope="module")
def world(tmp_path_factory):
    from variantcalling_tpu.synthetic import make_fixtures_fast, synthetic_forest

    d = str(tmp_path_factory.mktemp("bgzfobs"))
    make_fixtures_fast(d, n=N, genome_len=400_000, n_contigs=4)
    with open(f"{d}/calls.vcf", "rb") as fh, bgzf_mod.BgzfWriter(f"{d}/calls.vcf.gz") as w:
        w.write(fh.read())
    with open(f"{d}/model.pkl", "wb") as fh:
        pickle.dump({"m": synthetic_forest(np.random.default_rng(0), n_trees=8, depth=4)}, fh)
    return d


def run(world, monkeypatch, inp, out) -> dict:
    """One CLI run under obs; the log's events by kind."""
    from variantcalling_tpu.io import vcf as vcf_mod
    from variantcalling_tpu.pipelines.filter_variants import run as fvp_run

    monkeypatch.setattr(vcf_mod, "STREAM_CHUNK_BYTES", 1 << 16)
    monkeypatch.setenv("VCTPU_IO_THREADS", "2")
    monkeypatch.setenv("VCTPU_IO_SHARD_BYTES", str(1 << 17))
    monkeypatch.setenv("VCTPU_OBS", "1")
    rc = fvp_run(["--input_file", f"{world}/{inp}", "--model_file", f"{world}/model.pkl",
                  "--model_name", "m", "--reference_file", f"{world}/ref.fa",
                  "--output_file", out])
    with open(out + ".obs.jsonl", encoding="utf-8") as fh:
        events = [json.loads(ln) for ln in fh if ln.strip()]
    final = [e for e in events if e["kind"] == "metrics" and e["name"] == "final"]
    return {"rc": rc,
            "spans": [e for e in events if e["kind"] == "span"],
            "rows": {e["stage"]: e for e in events
                     if e["kind"] == "profile" and e["name"] == "stage"},
            "pipeline": [e for e in events
                         if e["kind"] == "profile" and e["name"] == "pipeline"],
            "counters": final[-1]["counters"]}


@pytest.fixture()
def plain_run(world, monkeypatch, tmp_path):
    out = str(tmp_path / "plain.vcf")
    return dict(run(world, monkeypatch, "calls.vcf", out), out=out)


@pytest.fixture()
def gz_run(world, monkeypatch, tmp_path):
    out = str(tmp_path / "out.vcf.gz")
    return dict(run(world, monkeypatch, "calls.vcf.gz", out), out=out)


def test_a_plain_text_run_emits_no_container_span_and_reads_zero(plain_run):
    assert plain_run["rc"] == 0
    assert not [s for s in plain_run["spans"] if s["name"] in SPANS]
    assert not [r for r in plain_run["rows"] if r.split(".")[0] in SPANS]
    assert {k: plain_run["counters"][k] for k in COUNTERS} == dict.fromkeys(COUNTERS, 0)
    assert not os.path.exists(plain_run["out"] + ".tbi")


@pytest.mark.parametrize("name", SPANS)
def test_a_gz_run_emits_the_span_and_its_row(gz_run, name):
    assert gz_run["rc"] == 0
    spans = [s for s in gz_run["spans"] if s["name"] == name]
    rows = [r for k, r in gz_run["rows"].items() if k.split(".")[0] == name]
    assert spans and rows
    assert sum(r["work_s"] for r in rows) == pytest.approx(
        sum(s["dur"] for s in spans), abs=1e-4)
    if name == "inflate":  # a pooled worker's row, as every pooled stage's
        assert all(k.startswith("inflate.w") for k in gz_run["rows"] if k.startswith("inflate"))
        assert sum(r["bytes_in"] for r in rows) == gz_run["counters"]["bgzf.in_bytes"]
        assert sum(r["bytes_out"] for r in rows) == gz_run["counters"]["bgzf.text_bytes_in"]
    elif name == "compress_stage":
        assert all(s["bytes_in"] > 0 and "bytes_out" in s for s in spans)
        assert sum(r["bytes_in"] for r in rows) > 0 < sum(r["bytes_out"] for r in rows)
    else:
        # a chunk's facts on the pooled worker that rendered it, their place
        # in the file on the compress stage's thread, the write on the
        # committer's, the only one with `bytes`
        pooled = {k: r for k, r in gz_run["rows"].items() if k.startswith("tabix_index.w")}
        assert pooled and sum(r["records"] for r in pooled.values()) == N
        assert gz_run["rows"]["tabix_index"]["work_s"] > 0
        (span,) = [s for s in spans if "bytes" in s]
        assert span["records"] == N and span["bytes"] == os.path.getsize(gz_run["out"])
        # the index is part of what the user waited for: inside the wall
        (pipe,) = gz_run["pipeline"]
        assert "tabix_index" in pipe["stages"] and set(pooled) <= set(pipe["stages"])
        assert pipe["wall_s"] > gz_run["rows"]["tabix_index"]["work_s"]
        assert span["start"] + span["dur"] <= max(
            s["start"] + s["dur"] for s in gz_run["spans"]) + 1e-6


def test_a_gz_runs_counters_agree_with_the_files(world, gz_run, plain_run):
    c = gz_run["counters"]
    with open(f"{world}/calls.vcf.gz", "rb") as fh:
        spans_in = bgzf_mod.scan_block_spans(fh.read())
    assert c["bgzf.in_blocks"] == len(spans_in)
    assert c["bgzf.in_bytes"] == os.path.getsize(f"{world}/calls.vcf.gz")
    assert c["bgzf.text_bytes_in"] == os.path.getsize(f"{world}/calls.vcf")
    assert c["bgzf.inflate_shards"] >= 2
    with open(gz_run["out"], "rb") as fh:
        data = fh.read()
    assert c["bgzf.out_bytes"] == len(data)
    assert c["bgzf.out_blocks"] == len(bgzf_mod.scan_block_spans(data))
    # the container holds the plain run's bytes, and the index its records
    assert c["bgzf.text_bytes_out"] == os.path.getsize(plain_run["out"])
    assert gzip.decompress(data) == open(plain_run["out"], "rb").read()
    assert c["tabix.records"] == N and c["tabix.index_skipped"] == 0
    assert (c["tabix.index_streamed"], c["tabix.index_second_pass"]) == (1, 0)
    assert os.path.exists(gz_run["out"] + ".tbi")


def test_an_output_that_cannot_be_indexed_is_counted_and_still_exits_0(
        world, monkeypatch, tmp_path, caplog):
    out = str(tmp_path / "noindex.vcf.gz")
    os.mkdir(out + ".tbi")  # the index cannot be written where a directory is
    got = run(world, monkeypatch, "calls.vcf.gz", out)
    assert got["rc"] == 0
    assert got["counters"]["tabix.index_skipped"] == 1
    assert got["counters"]["tabix.records"] == 0
    assert (got["counters"]["tabix.index_streamed"],
            got["counters"]["tabix.index_second_pass"]) == (0, 0)
    # the write that failed left no span; the gathering before it did
    assert not [s for s in got["spans"] if s["name"] == "tabix_index" and "bytes" in s]
    assert "no tabix index beside" in caplog.text
    assert len(gzip.decompress(open(out, "rb").read()).splitlines()) > N
