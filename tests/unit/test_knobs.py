"""Typed VCTPU_* knob registry: precedence, validation, typo warnings,
header provenance, and the uniform exit-2 contract across engines and
forest strategies (ISSUE 4 — extends the PR 3 ``validate_strategy_env``
tests to the whole registry)."""

from __future__ import annotations

import json

import pytest

from variantcalling_tpu import engine as engine_mod
from variantcalling_tpu import knobs
from variantcalling_tpu.engine import EngineError
from variantcalling_tpu.models.forest import FOREST_STRATEGIES


@pytest.fixture(autouse=True)
def _fresh_engine():
    engine_mod.reset_for_tests()
    yield
    engine_mod.reset_for_tests()


# ---------------------------------------------------------------------------
# registry shape + precedence
# ---------------------------------------------------------------------------


def test_every_knob_is_declared_with_help():
    assert len(knobs.REGISTRY) >= 25
    for name, knob in knobs.REGISTRY.items():
        assert name.startswith("VCTPU_")
        assert knob.help
        assert knob.kind in ("bool", "int", "float", "str", "enum")
        if knob.kind == "enum":
            assert knob.choices


def test_env_beats_default(monkeypatch):
    assert knobs.get_int("VCTPU_IO_RETRIES") == 2
    assert knobs.source("VCTPU_IO_RETRIES") == "default"
    monkeypatch.setenv("VCTPU_IO_RETRIES", "5")
    assert knobs.get_int("VCTPU_IO_RETRIES") == 5
    assert knobs.source("VCTPU_IO_RETRIES") == "env"


def test_empty_means_unset_except_str(monkeypatch):
    monkeypatch.setenv("VCTPU_IO_RETRIES", "")
    assert knobs.get_int("VCTPU_IO_RETRIES") == 2
    # str knobs keep the empty string as a value
    monkeypatch.setenv("VCTPU_COORDINATOR", "")
    assert knobs.get_str("VCTPU_COORDINATOR") == ""
    monkeypatch.delenv("VCTPU_COORDINATOR")
    assert knobs.get_str("VCTPU_COORDINATOR") is None


def test_bool_spellings(monkeypatch):
    for raw, want in [("1", True), ("true", True), ("YES", True),
                      ("on", True), ("0", False), ("false", False),
                      ("No", False), ("off", False)]:
        monkeypatch.setenv("VCTPU_OBS_TRACE", raw)
        assert knobs.get_bool("VCTPU_OBS_TRACE") is want


def test_typed_accessors_enforce_kind():
    with pytest.raises(TypeError, match="bool knob"):
        knobs.get_int("VCTPU_OBS_TRACE")
    with pytest.raises(KeyError):
        knobs.get("VCTPU_NOT_A_KNOB")
    with pytest.raises(KeyError):
        knobs.raw("VCTPU_NOT_A_KNOB")


# ---------------------------------------------------------------------------
# malformed values: EngineError everywhere, via the single parse point
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name,bad,match", [
    ("VCTPU_THREADS", "bogus", "not a positive integer"),
    ("VCTPU_THREADS", "0", "not a positive integer"),
    ("VCTPU_STREAM_CHUNK_BYTES", "-4", "not a positive integer"),
    ("VCTPU_IO_RETRIES", "two", "not an integer"),
    ("VCTPU_IO_RETRIES", "-1", "must be >= 0"),
    ("VCTPU_STAGE_TIMEOUT_S", "soon", "not a number"),
    ("VCTPU_STAGE_TIMEOUT_S", "-5", "must be >= 0"),
    ("VCTPU_ENGINE", "cuda", "not a valid engine"),
    ("VCTPU_FOREST_STRATEGY", "narrow", "not a valid forest strategy"),
    ("VCTPU_OBS_TRACE", "maybe", "not a valid boolean"),
])
def test_malformed_values_raise_engine_error(monkeypatch, name, bad, match):
    monkeypatch.setenv(name, bad)
    with pytest.raises(EngineError, match=match):
        knobs.get(name)
    with pytest.raises(EngineError, match=match):
        knobs.validate_all()


@pytest.mark.parametrize("engine", ["native", "jit"])
@pytest.mark.parametrize("strategy", FOREST_STRATEGIES)
def test_validate_all_uniform_across_engines_and_strategies(
        monkeypatch, engine, strategy):
    """The PR 3 rule, whole-registry: a malformed knob is the SAME
    configuration error no matter which engine or strategy the run
    pinned."""
    monkeypatch.setenv("VCTPU_ENGINE", engine)
    monkeypatch.setenv("VCTPU_FOREST_STRATEGY", strategy)
    monkeypatch.setenv("VCTPU_FASTA_CACHE_BYTES", "4g")
    with pytest.raises(EngineError, match="not an integer"):
        knobs.validate_all()


@pytest.mark.parametrize("engine", ["native", "jit"])
def test_filter_cli_exits_2_on_malformed_knob(monkeypatch, engine):
    """filter_variants.run validates the WHOLE registry before any work:
    a malformed execution knob (not just the strategy knobs PR 3
    covered) exits 2 on every engine, before the inputs are even
    opened."""
    from variantcalling_tpu.pipelines import filter_variants as fv

    monkeypatch.setenv("VCTPU_ENGINE", engine)
    monkeypatch.setenv("VCTPU_IO_BACKOFF_S", "fast")
    rc = fv.run(["--input_file", "/nonexistent.vcf",
                 "--model_file", "/nonexistent.pkl", "--model_name", "m",
                 "--reference_file", "/nonexistent.fa",
                 "--output_file", "/nonexistent.out.vcf"])
    assert rc == 2


# ---------------------------------------------------------------------------
# unknown-variable typo detection
# ---------------------------------------------------------------------------


def test_unknown_env_suggests_closest_knob(monkeypatch):
    monkeypatch.setenv("VCTPU_FOERST_STRATEGY", "wide")  # the ISSUE's typo
    unknown = dict(knobs.unknown_env())
    assert unknown["VCTPU_FOERST_STRATEGY"] == "VCTPU_FOREST_STRATEGY"


def test_warn_unknown_env_logs(monkeypatch, caplog):
    monkeypatch.setenv("VCTPU_FOERST_STRATEGY", "wide")
    monkeypatch.setenv("VCTPU_TOTALLY_NOVEL_THING", "1")
    with caplog.at_level("WARNING", logger="vctpu"):
        msgs = knobs.warn_unknown_env()
    assert any("VCTPU_FOERST_STRATEGY" in m and
               "did you mean VCTPU_FOREST_STRATEGY?" in m for m in msgs)
    assert any("VCTPU_TOTALLY_NOVEL_THING" in m for m in msgs)
    assert any("VCTPU_FOERST_STRATEGY" in r.message for r in caplog.records)


def test_registered_knobs_never_warn(monkeypatch):
    monkeypatch.setenv("VCTPU_FOREST_STRATEGY", "wide")
    assert all(k != "VCTPU_FOREST_STRATEGY" for k, _ in knobs.unknown_env())


# ---------------------------------------------------------------------------
# resolved dump + ##vctpu_knobs= header provenance
# ---------------------------------------------------------------------------


def test_resolved_lists_every_knob(monkeypatch):
    monkeypatch.setenv("VCTPU_WIDE_BLOCK", "8")
    rows = {name: (value, src) for name, value, src in knobs.resolved()}
    assert set(rows) == set(knobs.REGISTRY)
    assert rows["VCTPU_WIDE_BLOCK"] == (8, "env")
    assert rows["VCTPU_ENGINE"] == ("auto", "default")


def test_knobs_cli_dump_json(monkeypatch, capsys):
    monkeypatch.setenv("VCTPU_WIDE_CHUNK", "4096")
    assert knobs.run(["--json"]) == 0
    data = json.loads(capsys.readouterr().out)
    assert data["VCTPU_WIDE_CHUNK"] == {
        "value": 4096, "source": "env",
        "help": knobs.REGISTRY["VCTPU_WIDE_CHUNK"].help}


def test_knobs_cli_exits_2_on_malformed(monkeypatch, capsys):
    monkeypatch.setenv("VCTPU_WIDE_CHUNK", "4k")
    assert knobs.run([]) == 2
    assert "VCTPU_WIDE_CHUNK" in capsys.readouterr().err


def test_header_line_lists_only_set_scoring_knobs(monkeypatch):
    # nothing set: the line is present but empty (stale-line replacement)
    assert knobs.header_line() == "##vctpu_knobs="
    monkeypatch.setenv("VCTPU_WIDE_BLOCK", "8")
    monkeypatch.setenv("VCTPU_PALLAS", "0")
    # execution-only knobs must NOT appear: streaming/serial byte-parity
    monkeypatch.setenv("VCTPU_THREADS", "7")
    # engine-selection knobs are recorded via ##vctpu_engine= instead
    monkeypatch.setenv("VCTPU_ENGINE", "jit")
    assert knobs.header_line() == \
        "##vctpu_knobs=VCTPU_PALLAS=False,VCTPU_WIDE_BLOCK=8"


def test_filter_header_records_knobs(monkeypatch):
    from variantcalling_tpu.io.vcf import VcfHeader
    from variantcalling_tpu.pipelines.filter_variants import \
        _ensure_output_header

    monkeypatch.setenv("VCTPU_ENGINE", "jit")
    monkeypatch.setenv("VCTPU_WIDE_BLOCK", "8")
    header = VcfHeader()
    header.add_meta_line("##fileformat=VCFv4.2")
    header.add_meta_line("##vctpu_knobs=VCTPU_WIDE_BLOCK=4")  # stale input
    _ensure_output_header(
        header, engine=engine_mod.EngineDecision("jit", "jit", "t"),
        strategy="wide")
    lines = [line for line in header.lines
             if line.startswith("##vctpu_knobs=")]
    assert lines == ["##vctpu_knobs=VCTPU_WIDE_BLOCK=8"]


def test_filter_header_no_knobs_set_emits_nothing_and_strips_stale(monkeypatch):
    from variantcalling_tpu.io.vcf import VcfHeader
    from variantcalling_tpu.pipelines.filter_variants import \
        _ensure_output_header

    monkeypatch.delenv("VCTPU_WIDE_BLOCK", raising=False)
    header = VcfHeader()
    header.add_meta_line("##fileformat=VCFv4.2")
    header.add_meta_line("##vctpu_knobs=VCTPU_WIDE_BLOCK=4")  # stale input
    _ensure_output_header(
        header, engine=engine_mod.EngineDecision("jit", "jit", "t"))
    assert not [line for line in header.lines
                if line.startswith("##vctpu_knobs")]
