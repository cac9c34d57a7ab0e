"""One price table (PR 29): ``core.tiers`` prices a lattice edge at its
constant, whatever the environment holds, and the program's HBM peak is the
benchmark's.

Until PR 29 ``HEAT_TPU_LATTICE_PROFILE`` could name a measured profile that
replaced the constants (the only one ever made was a CPU container's). The
variable is retired; these cases set it to such a file and hold every price
to ``EDGES``.
"""

import json
import os

import pytest

from heat_tpu.core import tiers

from test_suites.basic_test import env_pin

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: edge -> what one of its bytes costs in ICI bytes (``tiers.penalty``)
PENALTY = {"hbm": 1, "pcie": 12, "ici": 1, "dcn": 8, "disk": 250}


@pytest.fixture
def stale_profile(tmp_path):
    """A profile file of the retired format, in the environment: every
    edge at 1 GB/s, so a reader of it would misprice all five."""
    path = tmp_path / "profile.json"
    path.write_text(json.dumps({
        "format": 1, "profile_id": "0" * 16, "platform": "cpu", "topology": "flat",
        "edges": {e: {"bps": 1e9, "method": "stale"} for e in tiers.EDGES},
    }))
    with env_pin("HEAT_TPU_LATTICE_PROFILE", str(path)):
        yield


def test_the_table_names_every_edge():
    assert set(PENALTY) == set(tiers.EDGES)


@pytest.mark.parametrize("edge", sorted(PENALTY))
def test_edge_price_is_the_constant(stale_profile, edge):
    bps = tiers.EDGES[edge][2]
    assert tiers.bandwidth(edge) == bps
    assert tiers.transfer_time(1 << 30, edge) == (1 << 30) / bps
    assert tiers.penalty(edge) == PENALTY[edge] == max(1, int(tiers.ICI_BPS / bps))


def test_hbm_peak_is_the_benchmarks():
    """The program's table and the yardstick's cannot part: the file is
    read, ``benchmarks`` is not imported (it imports nothing of the
    program's either)."""
    with open(os.path.join(ROOT, "benchmarks", "peaks.json")) as f:
        peaks = json.load(f)
    assert tiers.HBM_BPS == peaks["TPU v5 lite"]["hbm_bytes_per_s"]
