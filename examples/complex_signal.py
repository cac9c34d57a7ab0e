"""Complex arrays on a complex-less accelerator — the planar surface.

The TPU behind this environment has no XLA complex implementation, so
heat_tpu runs complex DNDarrays in PLANAR form: split real/imaginary f32
planes computed by ordinary XLA programs (``core/complex_planar.py``;
reference parity target: ``heat/core/complex_math.py``). This demo walks
a small quadrature-signal workload through the surface: factories →
arithmetic → ``complex_math`` → reductions → the Gauss 3-matmul — and
shows the actionable refusal for an op outside the surface.

    python examples/complex_signal.py                       # real TPU
    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python examples/complex_signal.py                   # 8-dev CPU mesh
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import heat_tpu as ht
from heat_tpu.core import devices


def main() -> None:
    ht.utils.place_compile_cache()
    # force the accelerator policy so the demo shows planar everywhere
    # (on the real TPU this is already the default)
    ht.use_complex("planar")
    print(f"complex mode: {devices.complex_mode()}")

    # a complex exponential sweep (quadrature signal), sharded over the mesh
    n = 4096
    t = np.linspace(0.0, 1.0, n).astype(np.float32)
    sig_np = np.exp(2j * np.pi * 40.0 * t).astype(np.complex64)
    sig = ht.array(sig_np, split=0)
    assert sig._is_planar and sig.split == 0
    print(f"signal: {sig.shape} {sig.dtype.__name__}, split={sig.split} (planar planes)")

    # complex_math surface (reference complex_math.py parity)
    inst_phase = ht.angle(sig)
    print(f"instantaneous phase range: [{float(inst_phase.min()):+.3f}, "
          f"{float(inst_phase.max()):+.3f}] rad")

    # demodulate: multiply by the conjugate carrier -> DC
    carrier = ht.array(np.exp(2j * np.pi * 40.0 * t).astype(np.complex64), split=0)
    base = sig * ht.conj(carrier)
    dc = ht.mean(base)
    print(f"demodulated mean (expect ~1+0j): {complex(dc):.4f}")

    # energy via the conjugate product, all on-device plane arithmetic
    energy = float(ht.sum((sig * ht.conj(sig)).real).numpy())
    print(f"signal energy (expect {n}): {energy:.1f}")

    # Gauss 3-matmul: a complex Gram matrix on the MXU
    m = ht.reshape(sig, (64, 64))
    gram = ht.matmul(m, ht.conj(m).resplit(None).T, precision="highest")
    oracle = sig_np.reshape(64, 64) @ np.conj(sig_np.reshape(64, 64)).T
    err = float(np.max(np.abs(gram.numpy() - oracle)))
    print(f"complex gram via 3 real MXU matmuls, max |err| vs numpy: {err:.2e}")

    # outside the surface: loud, actionable — never silently wrong
    try:
        ht.sort(sig)
    except TypeError as exc:
        print(f"sort refused as documented: {str(exc)[:72]}...")


if __name__ == "__main__":
    main()
