"""Test configuration: run the suite on a virtual 8-device CPU mesh.

The analog of the reference's ``mpirun -n N pytest heat/`` CI runs
(/root/reference/.github/workflows/ci.yaml:54-56): multi-device behavior is
exercised without hardware by forcing N host platform devices. Must run
before any jax backend initialization.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _flags:
    _flags += " --xla_force_host_platform_device_count=8"
if "--xla_cpu_collective_call_terminate_timeout_seconds" not in _flags:
    # XLA:CPU aborts the process when a collective's rendezvous waits 40 s.
    # Six xdist workers x 8 virtual devices on a loaded host get there
    # (the seed's run lost a worker to it): wait longer, the suite's own
    # time limit still bounds the run
    _flags += " --xla_cpu_collective_call_terminate_timeout_seconds=900"
if "--xla_cpu_multi_thread_eigen" not in _flags:
    # six workers x 8 virtual devices already oversubscribe the host:
    # intra-op thread pools on top of that only add contention (a
    # 12-file slice of the suite: 197 s -> 168 s with them off)
    _flags += " --xla_cpu_multi_thread_eigen=false"
os.environ["XLA_FLAGS"] = _flags.strip()
for _var in ("OMP_NUM_THREADS", "OPENBLAS_NUM_THREADS", "MKL_NUM_THREADS"):
    os.environ.setdefault(_var, "1")  # the numpy/scipy/torch oracles, likewise
