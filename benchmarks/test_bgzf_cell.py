"""CPU rehearsal of the ``.vcf.gz`` cell (``python -m pytest benchmarks/``):
the ``closed_loop_cli_bgzf`` driver, ``container_reference.py`` and the six
metrics that came with them, on what one traced ``--rehearse 100`` run of the
cell leaves behind. The container reference's own cases are tier-1
(``tests/unit/test_container_reference.py``)."""

import json
import os
import re
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

import run_cell  # noqa: E402

CELL = "forest-t40d6-hg38x2-vcfgz.wgs-batch-bgzf"
SEED = "3600000007"
NEW = ["inflate_work_share", "compress_work_share", "index_work_share",
       "bgzf_out_ratio", "bgzf_in_blocks_per_file", "tabix_index_skipped_per_file"]


@pytest.fixture(scope="module")
def traced():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("BENCH_RUN", None)
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run_cell.py"), "--workload", CELL,
         "--seed", SEED, "--seconds", "1", "--trace", "1", "--rehearse", "100"],
        env=env, capture_output=True, text=True, timeout=600, check=False)
    assert p.returncode == run_cell.EXIT_REHEARSAL and p.stdout.strip() == "", \
        p.stderr[-2000:]
    line = re.search(r"no result line: (\{.*\})\s*$", p.stderr, re.M)
    return {"stderr": p.stderr, "result": json.loads(line.group(1))}


def test_a_traced_rehearsal_is_correct_and_reports_the_new_metrics(traced):
    result = traced["result"]
    assert result["correct"] and result["failed"] == 0 and result["attempted"] >= 2
    m = {k: v["value"] for k, v in result["metrics"].items()}
    for name in NEW:
        assert isinstance(m.get(name), float), name
    assert m["inflate_work_share"] > 0 < m["compress_work_share"]
    assert m["index_work_share"] > 0
    assert 5 < m["bgzf_out_ratio"] < 50  # VCF text at level 6: about 4 to 1
    assert m["tabix_index_skipped_per_file"] == 0 and m["compiles_in_window"] == 0
    # every metric the plain-text forest cell reports of a file is read here too
    spec = run_cell.Spec(CELL)
    assert {x["name"] for x in spec.per_layer} - set(m) == set()


def test_every_output_was_held_to_the_container_and_left_as_plain_text(traced):
    details = run_cell.load_json(run_cell.OUT_DIR, f"{CELL}.seed{SEED}.trace1.json")
    cfg = run_cell.Spec(CELL).config
    for row in details["files"]:
        box = row["container"]
        assert row["rc"] == 0 and "broken" not in box
        assert box["payload_max"] <= 65536 and box["text_bytes_gzip"] == box["text_bytes"]
        assert box["size_ratio"] <= 1 + cfg["container"]["size_tolerance"]
        assert box["regions"] == cfg["container"]["index_regions_checked"] == 64
        assert box["regions_checked"] >= 32
        assert row["records"] == cfg["variants_per_file"] // 100
    assert "compressed 2 callsets" in traced["stderr"]
    left = os.listdir(os.path.join(run_cell.WORK, "run"))
    assert not [f for f in left if f.startswith("out_") or f.startswith("warm")]
