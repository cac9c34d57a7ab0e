"""The golden plan ids, one table (PR 29).

``plan_id`` is the sha1 of a plan's canonical serialization; it keys the
redistribution executor's program cache and the AOT store. So a change of
the plan format, of the planner's choices or of the lattice's prices is a
change of this table — a visible edit, never a drift. One case per row of
``scripts/redist_plans.py``'s flat dump: the golden specs at ``quant="0"``
and ``"int8"``, the staged plans, the factorization rings.

The ids were recorded at the parent commit of PR 29, which took the
measured-profile hook (``HEAT_TPU_LATTICE_PROFILE`` and the plan format's
``calibration`` key) out of the planner: that they all stand is the proof
that removing it changed no plan.
"""

import importlib.util
import json
import os

import pytest

from test_suites.basic_test import env_pin

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: row of the flat dump -> plan_id. A row the planner adds goes in here.
PLAN_IDS = {
    "noop_same_split": "a73577b2e204",
    "resplit_0_to_1_p8": "3fa7e27aefe5",
    "resplit_1_to_0_p8": "9dcceb241644",
    "resplit_0_to_1_int32_p4": "7da388bc1f4e",
    "resplit_uneven_p8": "785b5c64ef22",
    "resplit_3d_1_to_2_p8": "a4312eca02cb",
    "replicate_p8": "ba5015838a00",
    "slice_from_replicated_p8": "fd958543fa59",
    "mesh1_resplit": "ea8f4a542d36",
    "resplit_chunked_2gb_p8": "ac7c3d3bd0e2",
    "resplit_ring_8gb_p8": "9a9f6522afa0",
    "reshape_pivot_p8": "7e55bd63cf2f",
    "reshape_split0_local_p8": "06af6969c5a1",
    "reshape_gather_fallback_p8": "7187d492c0d5",
    "reshape_split1_1gb_p8": "e25264d7562c",
    "reshape_packed_rev_p8": "1424eb21252e",
    "reshape_lane_1gb_p8": "4f79dda1bad3",
    "resplit_1gb_p16": "6c06e58a4b8e",
    "reshape_split1_1gb_p16": "266f4c37f19f",
    "noop_same_split.quant": "a73577b2e204",
    "resplit_0_to_1_p8.quant": "3fa7e27aefe5",
    "resplit_1_to_0_p8.quant": "9dcceb241644",
    "resplit_0_to_1_int32_p4.quant": "7da388bc1f4e",
    "resplit_uneven_p8.quant": "785b5c64ef22",
    "resplit_3d_1_to_2_p8.quant": "a4312eca02cb",
    "replicate_p8.quant": "ba5015838a00",
    "slice_from_replicated_p8.quant": "fd958543fa59",
    "mesh1_resplit.quant": "ea8f4a542d36",
    "resplit_chunked_2gb_p8.quant": "f1da8a14748e",
    "resplit_ring_8gb_p8.quant": "165046f5c364",
    "reshape_pivot_p8.quant": "7e55bd63cf2f",
    "reshape_split0_local_p8.quant": "06af6969c5a1",
    "reshape_gather_fallback_p8.quant": "7187d492c0d5",
    "reshape_split1_1gb_p8.quant": "da6941a3d184",
    "reshape_packed_rev_p8.quant": "3952a3aa45f1",
    "reshape_lane_1gb_p8.quant": "90309a383363",
    "resplit_1gb_p16.quant": "05f0bc4303bf",
    "reshape_split1_1gb_p16.quant": "ee0a013d7651",
    "staged_hsvd_20gb_2pass": "68ac3eea14a2",
    "staged_hsvd_2gb_2pass": "170c0eea356c",
    "staged_hsvd_2gb_1pass": "fb4354932e4e",
    "staged_kmeans_2gb_stream": "f8899270cbf4",
    "staged_transform_4gb_writeback": "3055e44b13ac",
    "polar_f32_65536x1024_p8": "91c882055908",
    "cholesky_f32_8192_p8": "0e257a0a4fba",
    "lu_f32_8192_p8": "134cc63c3f8c",
    "solve_chol_f32_8192x256_p8": "51453578787a",
    "solve_lu_f32_8192x256_p8": "fcf9a92b3fe8",
}


@pytest.fixture(scope="module")
def flat_dump():
    """The flat dump as the script makes it, row name -> parsed plan. The
    retired profile variable is set to a path on purpose: no plan may
    read it."""
    spec = importlib.util.spec_from_file_location(
        "redist_plans", os.path.join(ROOT, "scripts", "redist_plans.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    with env_pin("HEAT_TPU_LATTICE_PROFILE", os.path.join(ROOT, "no-such-profile.json")):
        return {name: json.loads(s.canonical_json()) for name, s in mod.rows()}


def test_the_table_has_every_row_of_the_dump(flat_dump):
    assert list(flat_dump) == list(PLAN_IDS)


@pytest.mark.parametrize("row", list(PLAN_IDS))
def test_plan_id_is_the_recorded_one(flat_dump, row):
    plan = flat_dump[row]
    assert plan["plan_id"] == PLAN_IDS[row]
    assert "calibration" not in plan
