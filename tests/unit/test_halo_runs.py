"""Run-length scan + halo-exchange sequence parallelism (SURVEY §5.7)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from variantcalling_tpu.ops import runs as rops

# capability probe: the sharded halo scan builds an 8-way mesh
# (make_mesh(n_data=8)). conftest forces 8 virtual CPU devices, so these
# RUN in the suite; environments that cannot force a device count (or
# that strip XLA_FLAGS) skip with the reason instead of erroring in mesh
# construction.
# LAZY (a fixture, not an import-time skipif): jax.local_devices()
# initializes the backend, and collection must never pay that.


@pytest.fixture(scope="module")
def eight_devices():
    if len(jax.local_devices()) < 8:
        pytest.skip("capability probe: sharded halo scan needs >= 8 local "
                    "devices (--xla_force_host_platform_device_count=8)")


def _ref_run_lengths(codes):
    n = len(codes)
    out = np.zeros(n, dtype=np.int64)
    i = n - 1
    out[i] = 1
    for i in range(n - 2, -1, -1):
        out[i] = 1 + out[i + 1] if codes[i] == codes[i + 1] else 1
    return out


def test_run_lengths_matches_sequential_reference(rng):
    codes = rng.integers(0, 5, size=5000).astype(np.uint8)
    got = np.asarray(rops.run_lengths(jnp.asarray(codes)))
    np.testing.assert_array_equal(got, _ref_run_lengths(codes))
    starts = np.asarray(rops.run_starts(jnp.asarray(codes)))
    ref_starts = np.concatenate([[True], codes[1:] != codes[:-1]])
    np.testing.assert_array_equal(starts, ref_starts)


def test_find_runs_exact():
    codes = np.frombuffer(b"\x00\x00\x00\x01\x02\x02\x02\x02\x04\x04\x03", dtype=np.uint8).copy()
    # A*3  C  G*4  N*2  T  -> runs >= 3: A@0 len3, G@4 len4 (N excluded)
    starts, lengths = rops.find_runs(codes, min_length=3)
    np.testing.assert_array_equal(starts, [0, 4])
    np.testing.assert_array_equal(lengths, [3, 4])


def test_sharded_run_lengths_matches_single_device(rng, eight_devices):
    """8-shard halo-exchange scan == single-device scan, incl. runs that
    cross shard boundaries and a tail shorter than the dp multiple."""
    from variantcalling_tpu.parallel.halo import sharded_run_lengths
    from variantcalling_tpu.parallel.mesh import make_mesh

    n = 8 * 500 + 37  # non-divisible tail exercises the N padding
    codes = rng.integers(0, 4, size=n).astype(np.uint8)
    # plant a long run straddling the shard-0/shard-1 boundary (~position 503)
    codes[495:530] = 2
    mesh = make_mesh(n_data=8, n_model=1)
    starts, lengths = sharded_run_lengths(codes, mesh, halo=64)
    np.testing.assert_array_equal(lengths, _ref_run_lengths(codes))
    ref_starts = np.concatenate([[True], codes[1:] != codes[:-1]])
    np.testing.assert_array_equal(starts, ref_starts)


def test_sharded_halo_cap_documented(rng, eight_devices):
    """Runs longer than the halo report the cap (shard-local count + halo)."""
    from variantcalling_tpu.parallel.halo import sharded_run_lengths
    from variantcalling_tpu.parallel.mesh import make_mesh

    n = 8 * 100
    codes = np.zeros(n, dtype=np.uint8)
    codes[::2] = 1  # alternate to kill accidental runs
    codes[90:130] = 3  # 40-long run crossing shard edge at 100
    mesh = make_mesh(n_data=8, n_model=1)
    _, lengths = sharded_run_lengths(codes, mesh, halo=16)
    # at position 90, shard 0 sees 10 local + 16 halo bases of the run
    assert lengths[90] == 26
    # with a halo >= run remainder it is exact
    _, lengths2 = sharded_run_lengths(codes, mesh, halo=64)
    assert lengths2[90] == 40


def test_find_runs_bed_cli(tmp_path, rng):
    """End-to-end: FASTA -> runs BED, consumable by the filter pipeline's
    --runs_file reader; multi-device processes take the sharded scan."""
    from variantcalling_tpu.io.bed import read_bed
    from variantcalling_tpu.pipelines.misc import find_runs_bed

    base = rng.integers(0, 4, size=2000)
    # kill natural runs >= 4, then plant known ones
    for i in range(1, 2000):
        if base[i] == base[i - 1]:
            base[i] = (base[i] + 1) % 4
    seq = list("ACGT"[int(b)] for b in base)
    seq[100:112] = ["A"] * 12
    seq[99] = "C"; seq[112] = "G"
    seq[500:510] = ["T"] * 10
    seq[499] = "A"; seq[510] = "C"
    seq[800:805] = ["G"] * 5  # below threshold
    genome = "".join(seq)
    fa = tmp_path / "r.fa"
    fa.write_text(">chr9\n" + "\n".join(genome[i:i+60] for i in range(0, len(genome), 60)) + "\n")

    out = tmp_path / "runs.bed"
    assert find_runs_bed.run(["--reference", str(fa), "--output_bed", str(out),
                              "--min_length", "10"]) == 0
    iv = read_bed(str(out))
    got = sorted(zip(iv.start.tolist(), iv.end.tolist()))
    assert (100, 112) in got and (500, 510) in got
    assert all(e - s >= 10 for s, e in got)
    assert not any(s == 800 for s, _ in got)


def test_sharded_scan_n_runs_and_stitching(rng, eight_devices):
    """N-runs at sequence edges keep exact starts/lengths under sharding
    (out-of-band padding), and halo-capped runs stitch back to exact
    lengths through ops.runs.select_runs."""
    from variantcalling_tpu.ops.runs import select_runs
    from variantcalling_tpu.parallel.halo import sharded_run_lengths
    from variantcalling_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(n_data=8, n_model=1)
    n = 8 * 64
    codes = rng.integers(0, 3, size=n).astype(np.uint8)
    codes[:12] = 4   # leading N gap (real contigs start like this)
    codes[-12:] = 4  # trailing N gap
    starts, lengths = sharded_run_lengths(codes, mesh, halo=16)
    ref_starts = np.concatenate([[True], codes[1:] != codes[:-1]])
    np.testing.assert_array_equal(starts, ref_starts)
    assert lengths[0] == 12 and lengths[n - 12] == 12  # N padding must not extend them

    # a 200-long run crossing three shard edges: capped by halo=16, then
    # stitched to the exact length by select_runs
    codes2 = np.zeros(n, dtype=np.uint8)
    codes2[::2] = 1
    codes2[40:240] = 3
    starts2, lengths2 = sharded_run_lengths(codes2, mesh, halo=16)
    assert lengths2[40] < 200  # capped by construction
    idx, ln = select_runs(codes2, starts2, lengths2, min_length=10)
    assert 40 in idx.tolist()
    assert ln[idx.tolist().index(40)] == 200
