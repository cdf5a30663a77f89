"""The tile deal of a campaign packed straight onto several devices
(DESIGN.md §14): tile ``k`` of the packed order goes to device
``k % n_dev``, so every device holds its share of each (T, V) block.

One child process on four forced host devices packs, launches and
reports; the tests here read its report.  Bit-identity of the dealt
campaigns is pinned in ``tests/test_scale.py``.
"""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

from repro.campaign.grid import CELL_TILE, deal_tiles, undeal_tiles
from repro.launch.mesh import host_device_flag

REPO = Path(__file__).resolve().parents[1]

# the balance grid: 3 T x 2 V x 20,000 samples, 192 tiles over 4 devices
BALANCE_SAMPLES = 20_000

_CHILD = textwrap.dedent("""
    import json, sys
    import numpy as np
    import jax
    from repro.campaign import CampaignGrid, run_campaign
    from repro.campaign.grid import _pack_program, pack_campaign
    from repro.core.params import AFMTJ_PARAMS, CORNER_SS, CORNER_TT, VariationSpec
    from repro.runtime import telemetry

    assert jax.device_count() == 4, jax.devices()
    report = {}

    def grid(**kw):
        base = dict(voltages=(0.6, 1.2), pulse_widths=(2e-12,),
                    temperatures=(300.0, 350.0, 400.0), n_samples=1200,
                    dt=0.1e-12, seed=3100000171)
        base.update(kw)
        return CampaignGrid(**base)

    def dealt(g, **kw):
        before = telemetry.snapshot().get("campaign.dealt_launches", 0)
        run_campaign(AFMTJ_PARAMS, g, backend="ref", use_cache=False,
                     reduce="stream", n_bins=8, **kw)
        return telemetry.snapshot().get("campaign.dealt_launches", 0) - before

    report["four"] = dealt(grid(), devices=4)
    report["one"] = dealt(grid(), devices=1)
    report["variation"] = dealt(
        grid(variation=VariationSpec(corners=(CORNER_TT, CORNER_SS))),
        devices=4)
    report["split"] = dealt(grid(), devices=4, max_cells_per_launch=4096)
    for n_dev in (1, 4):
        text = _pack_program.lower(
            np.uint32(0), np.arange(3, dtype=np.int32),
            np.zeros(3, np.uint32), np.ones(3, np.float32),
            np.ones(3, np.float32), np.ones(2, np.float32), np.float32(9),
            p=AFMTJ_PARAMS, n_s=1200, n_dev=n_dev).as_text()
        report[f"pack_transposes_{n_dev}"] = text.count("stablehlo.transpose")

    # real lanes of every (T, V) block on each device, dealt and not
    big = grid(n_samples=int(sys.argv[2]))
    v_rows = np.float32(big.voltages)

    def blocks(state, sigma, budget, sigmas):
        out = []
        for st, sg, bd in zip(state, sigma, budget):
            real = bd > 0
            out.append([[int((real & (sg == s) & (st[6] == v)).sum())
                         for v in v_rows] for s in sigmas])
        return out

    st1, _, sg1, bd1, spans = pack_campaign(big, AFMTJ_PARAMS)
    sg1 = np.asarray(sg1)
    sigmas = [sg1[lo] for lo, _ in spans]
    assert len(set(sigmas)) == 3, sigmas
    quarter = sg1.size // 4
    cut = lambda x: [x[..., d * quarter:(d + 1) * quarter] for d in range(4)]
    report["contiguous"] = blocks(cut(np.asarray(st1)), cut(sg1),
                                  cut(np.asarray(bd1)), sigmas)
    st4, _, sg4, bd4, _ = pack_campaign(big, AFMTJ_PARAMS, n_dev=4)

    def shards(x):
        by_start = sorted(x.addressable_shards,
                          key=lambda s: s.index[-1].start or 0)
        return [np.asarray(s.data) for s in by_start]

    report["dealt"] = blocks(shards(st4), shards(sg4), shards(bd4), sigmas)
    with open(sys.argv[1], "w") as f:
        json.dump(report, f)
""")


@pytest.fixture(scope="module")
def report(tmp_path_factory):
    out = tmp_path_factory.mktemp("deal") / "report.json"
    env = {**os.environ, "PYTHONPATH": str(REPO / "src")}
    old = env.get("XLA_FLAGS", "").strip()
    env["XLA_FLAGS"] = f"{old} {host_device_flag(4)}".strip()
    r = subprocess.run(
        [sys.executable, "-c", _CHILD, str(out), str(BALANCE_SAMPLES)],
        env=env, capture_output=True, text=True, timeout=560)
    assert r.returncode == 0, r.stderr
    return json.loads(out.read_text())


@pytest.mark.parametrize("case, expected", [
    ("four", 1),                  # one launch, its lanes divide over 4
    ("one", 0),                   # devices=1
    ("variation", 0),             # corners pack eagerly, not dealt
    ("split", 0),                 # max_cells_per_launch: 3 launches
    ("pack_transposes_1", 0),     # the 1-device pack program has no deal
    ("pack_transposes_4", 4),     # one per lane-axis output of the pack
])
def test_deal_engages_only_where_intended(report, case, expected):
    assert report[case] == expected, (case, report[case])


def test_deal_gives_every_device_its_share_of_each_block(report):
    """Each device holds the even share of every (T, V) block's real lanes
    to within one tile; cut in contiguous quarters, the same block is far
    off it (the slow 0.6 V lanes pile onto the first devices)."""
    even = BALANCE_SAMPLES / 4
    dealt = np.asarray(report["dealt"])             # (device, T, V)
    contiguous = np.asarray(report["contiguous"])
    assert dealt.shape == contiguous.shape == (4, 3, 2), dealt.shape
    assert (dealt.sum(axis=0) == BALANCE_SAMPLES).all(), dealt.sum(axis=0)
    assert np.abs(dealt - even).max() <= CELL_TILE, dealt
    assert np.abs(contiguous - even).max() > 4 * CELL_TILE, contiguous


@pytest.mark.parametrize("lead", [(), (8,)])
def test_undeal_inverts_deal(lead):
    cols = 3 * 4 * CELL_TILE
    x = np.arange(int(np.prod(lead, dtype=int)) * cols).reshape(lead + (cols,))
    d = deal_tiles(x, 4)
    np.testing.assert_array_equal(undeal_tiles(d, 4), x)
    # tile k of the packed order sits in device k % 4's quarter
    tiles = d.reshape(lead + (4, 3, CELL_TILE))
    for k in range(12):
        np.testing.assert_array_equal(
            tiles[..., k % 4, k // 4, :],
            x[..., k * CELL_TILE:(k + 1) * CELL_TILE])
