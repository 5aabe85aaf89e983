"""The wire (variantcalling_tpu/wire.py): one static row layout per fused
program, two fills that write the same bytes, one unpack on the device, and
a staging pool that waits for the transfer."""

from __future__ import annotations

import hashlib
import os

import numpy as np
import pytest

import jax

from variantcalling_tpu import native, synthetic, wire
from variantcalling_tpu.featurize import (BASE_FEATURES, WINDOW_RADIUS,
                                          device_genome, globalize_positions,
                                          host_featurize, packed_position_fill)
from variantcalling_tpu.io import bed as bedio
from variantcalling_tpu.io.fasta import FastaReader
from variantcalling_tpu.io.vcf import read_vcf
from variantcalling_tpu.pipelines import filter_variants as fv

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="needs the native library")

CONTIGS = {"chr1": 700, "chr2": 400}
HEADER = "\n".join(
    ["##fileformat=VCFv4.2"]
    + [f"##contig=<ID={c},length={n}>" for c, n in CONTIGS.items()]
    + ['##INFO=<ID=DP,Number=1,Type=Float,Description="d">',
       '##INFO=<ID=SOR,Number=1,Type=Float,Description="s">',
       '##INFO=<ID=AF,Number=A,Type=Float,Description="a">',
       '##INFO=<ID=TLOD,Number=1,Type=Float,Description="t">',
       '##FORMAT=<ID=GT,Number=1,Type=String,Description="g">',
       '##FORMAT=<ID=GQ,Number=1,Type=Integer,Description="q">',
       '##FORMAT=<ID=AD,Number=R,Type=Integer,Description="a">',
       "#CHROM\tPOS\tID\tREF\tALT\tQUAL\tFILTER\tINFO\tFORMAT\tS"]) + "\n"


def _rec(chrom="chr1", pos=100, ref="A", alt="C", qual="37.5",
         info="DP=31;SOR=1.25;AF=0.5;TLOD=7.5", fmt="GT:GQ:AD", sample="0/1:44:10,12"):
    return "\t".join([chrom, str(pos), ".", ref, alt, qual, "PASS", info, fmt, sample])


PLAIN = [_rec(pos=50 + 7 * i, ref="ACGT"[i % 4], alt="CGTA"[i % 4],
              qual=f"{20 + i}.5", sample=f"{i % 2}/1:{30 + i}:{5 + i},{9 + i}")
         for i in range(40)]

#: name -> (records, keyword arguments of host_featurize)
CASES = {
    "plain": (PLAIN, {}),
    "unknown_contig": (PLAIN[:5] + [_rec(chrom="chrUn", pos=10)], {}),
    "past_contig_end": (PLAIN[:5] + [_rec(pos=CONTIGS["chr1"] + WINDOW_RADIUS + 1),
                                     _rec(pos=5000), _rec(chrom="chr2", pos=401 + WINDOW_RADIUS)], {}),
    "within_radius_of_contig_end": (PLAIN[:5] + [_rec(pos=CONTIGS["chr1"]),
                                                 _rec(pos=CONTIGS["chr1"] + 1),
                                                 _rec(pos=CONTIGS["chr1"] + WINDOW_RADIUS),
                                                 _rec(chrom="chr2", pos=1)], {}),
    "pos_zero": ([_rec(pos=0)] + PLAIN[:5], {}),
    "missing_qual": (PLAIN[:3] + [_rec(qual=".")], {}),
    "missing_gq": (PLAIN[:3] + [_rec(fmt="GT:AD", sample="0/1:3,4"),
                                _rec(fmt="GT:GQ:AD", sample="1/1:.:3,4")], {}),
    "missing_dp_sor": (PLAIN[:3] + [_rec(info="AF=0.25"), _rec(info="."),
                                    _rec(info="DP=.;SOR=.")], {}),
    "af_from_info_when_no_ad": (PLAIN[:3] + [_rec(info="DP=9;AF=0.125", fmt="GT", sample="0/1"),
                                             _rec(info="DP=9", fmt="GT:AD", sample="0/1:0,0"),
                                             _rec(info="DP=9", fmt="GT", sample="./.")], {}),
    "multi_allelic": (PLAIN[:3] + [_rec(alt="C,G", sample="1/2:20:1,5,6"),
                                   _rec(ref="AT", alt="A,ATT,G", sample="0/2:9:4,0,3")], {}),
    "insertion_over_255_bases": (PLAIN[:3] + [_rec(ref="A", alt="A" + "T" * 300),
                                              _rec(ref="A" + "CG" * 200, alt="A")], {}),
    "fractional_dp": (PLAIN[:3] + [_rec(info="DP=12.5;SOR=0.001;AF=1e-3")], {}),
    "keep_nan": (PLAIN[:3] + [_rec(qual="."), _rec(info="."),
                              _rec(fmt="GT", sample="./.")], {"keep_nan": True}),
    "annotate_intervals": (PLAIN, {"annotate_intervals": True}),
    "extra_info_tlod": (PLAIN[:4] + [_rec(info="DP=3")], {"extra_info_fields": ["TLOD"]}),
    "empty_chunk": ([], {}),
    "n_equal_to_bucket": ([_rec(pos=10 + (i % 600), qual=str(i % 97))
                           for i in range(1024)], {}),
}


@pytest.fixture(scope="module")
def world(tmp_path_factory):
    d = tmp_path_factory.mktemp("wire")
    rng = np.random.default_rng(3)
    with open(d / "ref.fa", "w") as fh:
        for c, n in CONTIGS.items():
            fh.write(f">{c}\n" + "".join("ACGT"[i] for i in rng.integers(0, 4, n)) + "\n")
    fasta = FastaReader(str(d / "ref.fa"))
    return {"dir": d, "fasta": fasta, "genome": device_genome(fasta)}


def _table(world, name, records):
    path = world["dir"] / f"{name}.vcf"
    path.write_text(HEADER + "".join(r + "\n" for r in records))
    table = read_vcf(str(path))
    assert table.aux is not None  # through the native parser
    return table


def _featurize_args(kw):
    kw = dict(kw)
    if kw.get("annotate_intervals"):
        kw["annotate_intervals"] = {
            "lcr": bedio.IntervalSet(np.asarray(["chr1", "chr2"], dtype=object),
                                     np.asarray([60, 0]), np.asarray([120, 50]))}
    return kw


def _both_fills(world, table, kw, resident, rows=1024):
    """(native, numpy): two staging buffers of one layout, filled and padded."""
    fasta, genome = world["fasta"], world["genome"] if resident else None
    kw = _featurize_args(kw)
    full = host_featurize(table, fasta, compute_windows=False, **kw)
    part = host_featurize(table, fasta, compute_windows=False, base_columns=False, **kw)
    assert part.alle is None and part.names == full.names
    layout = wire.layout_for(tuple(fv._host_names(full.names)), resident)
    assert set(part.cols) == set(layout.extras)
    n = len(table)
    fill = packed_position_fill(genome) if resident else 0
    a, b = wire.Staging(rows, layout), wire.Staging(rows, layout)
    wire.fill_native(a, 0, table, 0, n, part.cols, genome, part.keep_nan)
    gpos = globalize_positions(table, genome) if resident and n else \
        np.empty(0, np.uint32)
    wire.fill_numpy(b, 0, wire.numpy_columns(layout, full, gpos), 0, n)
    a.pad_from(n, fill)
    b.pad_from(n, fill)
    return a, b, full, gpos


# -- (a) the two fills write the same bytes ----------------------------------


@pytest.mark.parametrize("resident", [True, False], ids=["resident", "host_windows"])
@pytest.mark.parametrize("case", list(CASES))
def test_native_and_numpy_fill_write_equal_bytes(world, case, resident):
    records, kw = CASES[case]
    table = _table(world, case, records)
    assert len(table) == len(records)
    a, b, full, _gpos = _both_fills(world, table, kw, resident)
    assert a.words.tobytes() == b.words.tobytes()
    n = len(table)
    # and they are the columns host_featurize made, value for value
    for name in a.layout.host_names:
        want = np.asarray(full.cols[name])
        got = a.rec[name][:n]
        assert np.array_equal(got.astype(np.float32), want.astype(np.float32),
                              equal_nan=True), name
    if n < a.rows:
        pad = a.rec[n:]
        assert (pad["indel_nuc"] == 4).all() and (pad["ref_code"] == 4).all() \
            and (pad["qual"] == 0).all()
        if resident:
            assert (pad["pos"] == packed_position_fill(world["genome"])).all()


def test_native_fill_writes_rows_at_an_offset_and_only_those(world):
    """A megabatch: two chunks' rows one after the other in one buffer."""
    t1 = _table(world, "mb1", PLAIN[:7])
    t2 = _table(world, "mb2", CASES["multi_allelic"][0])
    whole = _table(world, "mb", PLAIN[:7] + CASES["multi_allelic"][0])
    genome = world["genome"]
    layout = wire.layout_for(tuple(fv._host_names(list(BASE_FEATURES))), True)
    one, two = wire.Staging(1024, layout), wire.Staging(1024, layout)
    wire.fill_native(one, 0, whole, 0, len(whole), {}, genome, False)
    wire.fill_native(two, 0, t1, 0, len(t1), {}, genome, False)
    wire.fill_native(two, len(t1), t2, 0, len(t2), {}, genome, False)
    n = len(whole)
    assert one.words[:n].tobytes() == two.words[:n].tobytes()
    assert not two.words[n:].any()  # untouched
    # a slice of a table lands where it is told to
    wire.fill_native(two, 500, whole, 3, 9, {}, genome, False)
    assert two.words[500:506].tobytes() == one.words[3:9].tobytes()


def test_pad_rows_are_rewritten_only_where_a_fill_left_data(world):
    layout = wire.layout_for(tuple(fv._host_names(list(BASE_FEATURES))), True)
    buf = wire.Staging(64, layout)
    buf.rec["qual"][:40] = 7.0
    buf.pad_from(40, 1000)
    assert (buf.rec["pos"][40:] == 1000).all() and (buf.rec["qual"][40:] == 0).all()
    buf.rec["qual"][:10] = 3.0
    buf.rec["qual"][50:] = 9.0  # past what the last fill wrote: must survive
    buf.pad_from(10, 1000)
    assert (buf.rec["qual"][10:50] == 0).all() and (buf.rec["qual"][50:] == 9.0).all()
    buf.pad_from(10, 2000)  # another genome's fill: every pad row again
    assert (buf.rec["pos"][10:] == 2000).all() and (buf.rec["qual"][10:] == 0).all()


def test_layout_is_static_forty_bytes_and_sends_each_column_once():
    layout = wire.layout_for(tuple(fv._host_names(list(BASE_FEATURES))), True)
    assert layout.width == 40 and layout.words == 10
    assert sorted(layout.dtype.names) == sorted(
        ["pos", "indel_nuc"] + fv._host_names(list(BASE_FEATURES)))
    assert wire.layout_for(tuple(fv._host_names(list(BASE_FEATURES))), True) is layout
    host = wire.layout_for(tuple(fv._host_names(list(BASE_FEATURES))), False)
    assert "pos" not in host.dtype.names and host.width == 36
    extra = wire.layout_for(tuple(fv._host_names(list(BASE_FEATURES) + ["tlod", "lcr"])), True)
    assert extra.extras == ("tlod", "lcr") and extra.width == 48
    # every 32-bit column is one whole word of the row
    for name in extra.dtype.names:
        dtype, off = extra.dtype.fields[name][:2]
        assert dtype.itemsize == 1 or off % 4 == 0


# -- (b) the unpack gives the program what the 19 arguments gave it -----------


def _expected_arguments(full, gpos):
    """What the parent's call handed the program, after its ``astype``s."""
    want = {f: np.asarray(full.cols[f]).astype(np.float32)
            for f in fv._host_names(full.names)}
    alle = full.alle
    want.update({"is_indel!": alle.is_indel.astype(bool),
                 "indel_nuc!": alle.indel_nuc.astype(np.int32),
                 "ref_code!": alle.ref_code.astype(np.int32),
                 "alt_code!": alle.alt_code.astype(np.int32),
                 "is_snp!": alle.is_snp.astype(bool)})
    if gpos is not None:
        want["pos!"] = gpos.astype(np.uint32)
    return want


def _unpacked_arguments(layout, words, mesh=None):
    import jax.numpy as jnp

    def program(words):
        col = wire.unpack(layout, words)
        out = {f: col[f].astype(jnp.float32) for f in layout.host_names}
        out.update({"is_indel!": col["is_indel"].astype(bool),
                    "indel_nuc!": col["indel_nuc"].astype(jnp.int32),
                    "ref_code!": col["ref_code"].astype(jnp.int32),
                    "alt_code!": col["alt_code"].astype(jnp.int32),
                    "is_snp!": col["is_snp"].astype(bool)})
        if layout.resident:
            out["pos!"] = col["pos"]
        return out

    if mesh is None:
        return jax.jit(program)(words)
    from variantcalling_tpu.parallel import shard_score
    from variantcalling_tpu.parallel.mesh import data_sharding

    sharded = shard_score.shard_program(program, mesh, n_data_args=1)
    return jax.jit(sharded)(jax.device_put(words, data_sharding(mesh, 2)))


@pytest.mark.parametrize("devices", [1, 2], ids=["one_device", "two_device_mesh"])
@pytest.mark.parametrize("resident", [True, False], ids=["resident", "host_windows"])
@pytest.mark.parametrize("features", ["forest_with_extras", "dan_base"])
def test_unpack_gives_the_program_the_parents_arguments(world, features, resident, devices):
    if devices > len(jax.devices()):
        pytest.skip("needs two devices")
    records = PLAIN + CASES["multi_allelic"][0][3:] + CASES["insertion_over_255_bases"][0][3:] \
        + CASES["fractional_dp"][0][3:]
    kw = {"annotate_intervals": True, "extra_info_fields": ["TLOD"]} \
        if features == "forest_with_extras" else {}
    table = _table(world, f"unpack_{features}", records)
    a, _b, full, gpos = _both_fills(world, table, kw, resident)
    mesh = None
    if devices > 1:
        from variantcalling_tpu.parallel import shard_score

        mesh = shard_score.mesh_for(shard_score.MeshPlan(devices, str(devices), "test"))
    got = _unpacked_arguments(a.layout, a.words, mesh)
    want = _expected_arguments(full, gpos if resident else None)
    assert set(got) == set(want)
    n = len(table)
    for name, w in want.items():
        g = np.asarray(got[name])
        assert g.dtype == w.dtype, name
        assert np.array_equal(g[:n], w, equal_nan=True), name


# -- (c) no chunk's contents cause a trace -------------------------------------


@pytest.mark.parametrize("case", ["insertion_over_255_bases", "fractional_dp"])
def test_a_wide_value_in_a_chunk_causes_no_new_trace(world, case):
    """The parent chose each column's wire dtype from the chunk's contents
    (a 300-base insertion or a fractional DP turned a uint8 column into
    float32) and that dtype was in the trace signature; the layout is static."""
    from variantcalling_tpu.parallel import shard_score

    model = synthetic.synthetic_forest(np.random.default_rng(1), n_trees=4, depth=3)
    fasta = world["fasta"]
    plan = shard_score.MeshPlan(1, "1", "test")

    def score(table):
        hf = host_featurize(table, fasta, compute_windows=False, base_columns=False)
        fi = fv._prepare_fused_inputs(model, hf, "TGCA", table=table, fasta=fasta, plan=plan)
        return fi.program[0], fv._dispatch_fused([fi], plan)

    fn, first = score(_table(world, "trace_plain", PLAIN))
    called, compiled = set(fv._CALLED_AT[fn]), fn._cache_size()
    wide = _table(world, f"trace_{case}", CASES[case][0])
    full = host_featurize(wide, fasta, compute_windows=False)
    assert full.cols["indel_length"].max() > 255 or (full.cols["dp"] % 1 != 0).any()
    fn2, second = score(wide)
    assert fn2 is fn and len(second) == len(wide) and len(first) == len(PLAIN)
    assert set(fv._CALLED_AT[fn]) == called and fn._cache_size() == compiled == 1


# -- (d) the pipeline's bytes are the parent's ---------------------------------

#: sha256 of the output of the parent commit (5d65fdd) on
#: synthetic.make_fixtures(n=6000, genome_len=120_000), VCTPU_ENGINE=jit
PINNED = {
    "forest": "d900e7a81db2a8750c391fa95d6bf9ca2edf72cb6ef0365dd4f7d9c3802682ad",
    "dan": "55d861da8aa428274ac78571512df24bc175dc6933cc9981eda73d346e988317",
}


@pytest.fixture(scope="module")
def pipeline_world(tmp_path_factory):
    from variantcalling_tpu.models import registry

    d = str(tmp_path_factory.mktemp("wire_pipeline"))
    synthetic.make_fixtures(d, n=6000, genome_len=120_000)
    registry.save_models(d + "/model.pkl", {
        "forest": synthetic.synthetic_forest(np.random.default_rng(1), n_trees=8, depth=5),
        "dan": synthetic.synthetic_dan(np.random.default_rng(2), list(BASE_FEATURES))})
    return d


@pytest.mark.parametrize("name,chunk_bytes,resident", [
    ("forest", 1 << 14, False), ("forest", 1 << 16, False), ("forest", 8 << 20, False),
    ("dan", 1 << 16, False), ("forest", 1 << 15, True), ("dan", 8 << 20, True)])
def test_pipeline_output_equals_the_parents_pinned_digest(
        pipeline_world, monkeypatch, tmp_path, name, chunk_bytes, resident):
    from variantcalling_tpu import featurize, obs

    d = pipeline_world
    featurize._DEVICE_GENOME_CACHE.clear()
    if resident:  # a genome already on the device is used at any chunk size
        device_genome(FastaReader(d + "/ref.fa"))
    monkeypatch.setenv("VCTPU_ENGINE", "jit")
    monkeypatch.setenv("VCTPU_STREAM_CHUNK_BYTES", str(chunk_bytes))
    if name == "dan":
        monkeypatch.setenv("VCTPU_MODEL_FAMILY", "dan")
    run = obs.start_run("test_wire", force_path=str(tmp_path / "obs.jsonl"))
    out = f"{d}/out_{name}_{chunk_bytes}.vcf"
    rc = fv.run(["--input_file", d + "/calls.vcf", "--model_file", d + "/model.pkl",
                 "--model_name", name, "--reference_file", d + "/ref.fa",
                 "--output_file", out, "--backend", "cpu"])
    counters = run.metrics.snapshot()["counters"]
    obs.end_run(run, "ok")
    assert rc == 0
    with open(out, "rb") as fh:
        assert hashlib.sha256(fh.read()).hexdigest() == PINNED[name]
    # every dispatch was one buffer beside the windows, filled natively
    assert counters["feed.dispatches"] >= 1
    assert counters["feed.native_fills"] == counters["feed.dispatches"]
    assert counters["feed.numpy_fills"] == 0
    assert counters["feed.h2d_arrays"] == (1 if resident else 2) * counters["feed.dispatches"]
    # real rows sent, and the rows of the buckets they rode: a bucket a chunk
    from variantcalling_tpu.io.vcf import VcfChunkReader

    chunks = [len(t) for t in VcfChunkReader(d + "/calls.vcf", chunk_bytes=chunk_bytes,
                                             io_threads=1)]
    assert counters["feed.rows"] == counters["records"] == sum(chunks) == 6000
    assert counters["feed.padded_rows"] == sum(featurize._bucket(n) for n in chunks)
    assert os.path.getsize(out) > 0


# -- (e) the pool waits for the transfer ---------------------------------------


class _Transfer:
    """A stubbed transferred array: ready when the test says so."""

    def __init__(self):
        self.done = False

    def is_ready(self):
        return self.done


def test_pool_hands_a_buffer_out_again_only_after_its_transfer_completed():
    layout = wire.layout_for(tuple(fv._host_names(list(BASE_FEATURES))), True)
    pool = wire.StagingPool()
    first = pool.take(1024, layout)
    copy = _Transfer()
    pool.give(first, (copy,))
    second = pool.take(1024, layout)
    assert second is not first  # the copy may still read it
    copy.done = True
    assert pool.take(1024, layout) is first and len(pool) == 0
    # both arrays of a host-windows dispatch must have landed
    a, b = _Transfer(), _Transfer()
    pool.give(first, (a, b))
    a.done = True
    assert pool.take(1024, layout) is not first
    b.done = True
    assert pool.take(1024, layout) is first
    # an idle buffer keeps no device array alive once its copy has landed
    import weakref

    landed = _Transfer()
    ref = weakref.ref(landed)
    landed.done = True
    pool.give(first, (landed,))
    del landed
    pool.give(second, ())
    assert ref() is None


def test_pool_keys_on_rows_and_layout_and_bounds_its_idle_bytes():
    base = tuple(fv._host_names(list(BASE_FEATURES)))
    resident, host = wire.layout_for(base, True), wire.layout_for(base, False)
    pool = wire.StagingPool(max_bytes=3 * 1024 * 40)
    bufs = [pool.take(1024, resident) for _ in range(5)]
    for b in bufs:
        pool.give(b, ())
    assert len(pool) == 3  # the oldest were dropped, not kept
    assert pool.take(2048, resident).rows == 2048 and len(pool) == 3
    other = pool.take(1024, host)
    assert other.layout is host and other.windows.shape == (1024, 41) and len(pool) == 3
    assert pool.take(1024, resident) is bufs[2]


def test_a_buffer_whose_result_failed_is_never_reused():
    class Failed:
        def is_ready(self):
            raise RuntimeError("the computation failed")

    layout = wire.layout_for(tuple(fv._host_names(list(BASE_FEATURES))), True)
    pool = wire.StagingPool()
    buf = pool.take(1024, layout)
    pool.give(buf, (Failed(),))
    assert pool.take(1024, layout) is not buf


def test_put_reads_host_memory_is_found_by_writing_after_the_copy():
    """On the CPU client an aligned array is read in place, so there the
    dispatch ties a buffer to the program's result."""
    assert wire.put_reads_host_memory() in (True, False)
    if jax.default_backend() == "cpu":
        assert wire.put_reads_host_memory("cpu") is True


@pytest.mark.parametrize("keep_nan", [True, False], ids=["keep_nan", "nan_to_zero"])
@pytest.mark.parametrize("case", ["plain", "missing_qual", "missing_gq", "missing_dp_sor",
                                  "af_from_info_when_no_ad", "keep_nan", "extra_info_tlod"])
def test_both_fills_count_the_nan_cells_they_write(world, case, keep_nan):
    """Each fill returns the float32 cells it wrote as NaN, counted only
    where NaN is kept: what ``feed.nan_cells`` adds up."""
    records, kw = CASES[case]
    table = _table(world, f"count_{case}", records)
    kw = dict(_featurize_args(kw), keep_nan=keep_nan)
    fasta, genome, n = world["fasta"], world["genome"], len(table)
    full = host_featurize(table, fasta, compute_windows=False, **kw)
    part = host_featurize(table, fasta, compute_windows=False, base_columns=False, **kw)
    layout = wire.layout_for(tuple(fv._host_names(full.names)), True)
    assert {"qual", "dp", "sor", "af", "gq"} <= set(layout.floats)
    a, b = wire.Staging(1024, layout), wire.Staging(1024, layout)
    got_native = wire.fill_native(a, 0, table, 0, n, part.cols, genome, keep_nan)
    got_numpy = wire.fill_numpy(b, 0, wire.numpy_columns(
        layout, full, globalize_positions(table, genome)), 0, n, keep_nan)
    written = sum(int(np.isnan(a.rec[name][:n]).sum()) for name in layout.floats)
    assert got_native == got_numpy == (written if keep_nan else 0)
    if not keep_nan:
        assert written == 0
    elif case == "keep_nan":  # by hand: QUAL; DP and SOR (AF comes from AD); GQ
        assert written == 4
