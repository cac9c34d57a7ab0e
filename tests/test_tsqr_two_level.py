"""Two-level TSQR (r5): at mesh widths ≥ 16 the R-factor merge runs as a
group tree — all-gather WITHIN each √p-wide group, merge, all-gather the
group R's ACROSS groups, merge — cutting ICI bytes and replicated merge
FLOPs from p·K² to (s + p/s)·K² (docs/PERF.md named the flat merge's
(p·r)² growth as the mesh-width wall; this is the promised fix).

The suite's 8-device mesh keeps the flat single-gather schedule (its HLO
contract is pinned elsewhere), so the two-level path is exercised in a
SUBPROCESS forcing 16 host devices — the same pattern test_x64_policy
uses for the degraded mode."""

import os
import subprocess
import sys

import pytest

_WORKER = r"""
import jax
jax.config.update('jax_platforms', 'cpu')
import numpy as np
import jax.numpy as jnp
import heat_tpu as ht
from heat_tpu.core.linalg.qr import _tsqr_fn, _tsqr_group_size

comm = ht.get_comm()
assert comm.size == 16, comm.size
assert _tsqr_group_size(16) == 4

rng = np.random.default_rng(0)
# QR parity incl. uneven (padded) rows
for m, n in ((16 * 40, 24), (16 * 33 + 5, 16), (16 * 8, 8)):
    a = rng.standard_normal((m, n)).astype(np.float32)
    q, r = ht.linalg.qr(ht.array(a, split=0))
    qn, rn = q.numpy(), r.numpy()
    assert np.allclose(qn @ rn, a, atol=1e-4), (m, n)
    assert np.allclose(qn.T @ qn, np.eye(qn.shape[1]), atol=1e-4), (m, n, 'orth')
    assert np.allclose(np.triu(rn), rn, atol=1e-5), (m, n, 'upper')

# HLO contract: exactly TWO all-gathers (one per tree level), no other
# collectives — and each carries s*K^2 / (p/s)*K^2, never the operand
fn = _tsqr_fn(comm.mesh, comm.axis_name, 40, 24, 'float32', True)
phys = comm.shard(jnp.ones((16 * 40, 24), jnp.float32), 0)
txt = fn.lower(phys).compile().as_text()
ag_lines = [l for l in txt.splitlines() if ' all-gather(' in l or 'all-gather-start(' in l]
assert len(ag_lines) == 2, len(ag_lines)
assert ' all-to-all(' not in txt
assert ' collective-permute(' not in txt
# the gathers carry s*K^2 and (p/s)*K^2 floats — never the operand
import re
K, s_w, G_w = 24, 4, 4
sizes = sorted(
    int(np.prod([int(d) for d in re.search(r'f32\[([\d,]+)\]', l).group(1).split(',')]))
    for l in ag_lines
)
assert sizes == sorted([s_w * K * K, G_w * K * K]), sizes

# hSVD merges through the same TSQR: the tree must be invisible to it
lr = (rng.standard_normal((16 * 24, 6)) @ rng.standard_normal((6, 128))).astype(np.float32)
u, s, v, err = ht.linalg.hsvd_rank(ht.array(lr, split=0), 8, compute_sv=True)
rec = (u.numpy() * s.numpy()) @ v.numpy().T
assert np.linalg.norm(rec - lr) / np.linalg.norm(lr) < 1e-3

# collective-matmul form (ISSUE 6): BOTH tree levels decompose into
# grouped ppermute rings — (s-1) + (G-1) = 6 hops, zero all-gathers —
# and Q/R stay bit-identical to the barrier form (the rings assemble
# the identical stacked R arrays)
fn_ring = _tsqr_fn(comm.mesh, comm.axis_name, 40, 24, 'float32', True, ring=True)
txt_r = fn_ring.lower(phys).compile().as_text()
assert ' all-gather(' not in txt_r and 'all-gather-start(' not in txt_r
n_cp = txt_r.count(' collective-permute(') + txt_r.count('collective-permute-start(')
assert n_cp == (s_w - 1) + (G_w - 1), n_cp
a = rng.standard_normal((16 * 40, 24)).astype(np.float32)
pa = comm.shard(jnp.asarray(a), 0)
qg, rg = fn(pa)
qr_, rr_ = fn_ring(pa)
assert (np.asarray(qg) == np.asarray(qr_)).all()
assert (np.asarray(rg) == np.asarray(rr_)).all()

print('TSQR_TWO_LEVEL_OK')
"""


# slow: ~23 s in a subprocess; runs in scripts/ci.sh's full leg
@pytest.mark.slow
def test_two_level_tsqr_subprocess():
    env = {k: v for k, v in os.environ.items() if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
    out = subprocess.run(
        [sys.executable, "-c", _WORKER], capture_output=True, text=True, env=env,
        timeout=600,
    )
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    assert "TSQR_TWO_LEVEL_OK" in out.stdout
