"""chip_smoke.py rehearsed on the CPU mesh (on-chip-measurement guide 2.1-2.2).

``--rehearse`` runs every phase of the script at toy sizes on virtual CPU
devices, kernels in interpret mode: wrong paths, arguments, meshes and
sharding rules show here, at no chip time. Its last line names the
platform JAX really used, so it can never be read as a chip pass — and
the plain invocation, which is what the driver runs, must fail here.
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")
ONE_CHIP = ["dispatch", "matmul", "hsvd", "qr", "kmeans", "sort", "train_step", "attention",
            "dispatch.native_complex64"]
FOUR_CHIPS = ["mesh.hsvd", "mesh.resplit", "mesh.sort", "mesh.matmul", "mesh.train_step"]


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    """The three invocations, started together (each is compile-bound):
    {name: (returncode, stdout lines, stderr)}."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    # the rehearsals' CPU programs do not belong in the checkout's cache
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path_factory.mktemp("jax_cache"))
    argv = {
        "one": ["--rehearse"],
        "four": ["--rehearse", "--chips", "4"],
        "plain": [],
    }
    procs = {
        name: subprocess.Popen(
            [sys.executable, SMOKE, *args], cwd=ROOT, env=env, text=True,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        )
        for name, args in argv.items()
    }
    out = {"cache_dir": env["JAX_COMPILATION_CACHE_DIR"]}
    try:
        for name, p in procs.items():
            stdout, stderr = p.communicate(timeout=600)
            out[name] = (p.returncode, stdout.strip().splitlines(), stderr)
    finally:
        for p in procs.values():
            if p.poll() is None:
                p.kill()
                p.wait()
    return out


@pytest.mark.parametrize("name,chips,phases", [("one", 1, ONE_CHIP), ("four", 4, FOUR_CHIPS)])
def test_rehearsal_passes_every_phase(runs, name, chips, phases):
    rc, lines, stderr = runs[name]
    assert rc == 0, stderr[-3000:]
    recs = [json.loads(line) for line in lines]
    by_phase = {r["phase"]: r for r in recs if "phase" in r}
    assert [r["phase"] for r in recs[1:-1]] == phases
    for ph in phases:
        assert by_phase[ph]["ok"] is True, by_phase[ph]
        if "max_err" in by_phase[ph]:
            assert by_phase[ph]["max_err"] <= by_phase[ph]["tol"]
    # the real platform, never "tpu": a rehearsal is not a chip pass
    assert recs[-1] == {"ok": True, "device": {"platform": "cpu", "kind": "cpu", "count": chips}}
    # the compile cache was placed from outside: used, and nothing else set
    assert recs[0]["compile_cache"] == runs["cache_dir"] and os.listdir(runs["cache_dir"])


def test_four_chip_rehearsal_places_and_counts(runs):
    """What only the mesh path shows: four distinct devices holding the
    comm.chunk geometry, and the collective census of each program."""
    _, lines, _ = runs["four"]
    by_phase = {r["phase"]: r for r in map(json.loads, lines) if "phase" in r}
    shards = by_phase["mesh.hsvd"]["placement"]["shards"]
    assert by_phase["mesh.hsvd"]["placement"]["distinct_devices"] == 4
    assert all(s["shard"] == s["chunk"] for s in shards)
    assert [s["chunk_offset"] for s in shards] == sorted({s["chunk_offset"] for s in shards})
    assert by_phase["mesh.resplit"]["collectives"] == {"all-to-all": 2}
    assert by_phase["mesh.train_step"]["collectives"] == {"all-reduce": 1}
    assert by_phase["mesh.sort"]["collectives"].get("all-gather", 0) == 0


def test_place_compile_cache():
    """Placed from outside (JAX reads JAX_COMPILATION_CACHE_DIR into its
    configuration) -> sets nothing; else <checkout>/.jax_cache with a
    compile-time floor low enough to keep the smoke's programs."""
    import jax

    from heat_tpu.utils import place_compile_cache

    saved = (jax.config.jax_compilation_cache_dir,
             jax.config.jax_persistent_cache_min_compile_time_secs)
    try:
        jax.config.update("jax_compilation_cache_dir", "/placed/from/outside")
        assert place_compile_cache() == "/placed/from/outside"
        assert jax.config.jax_persistent_cache_min_compile_time_secs == saved[1]
        jax.config.update("jax_compilation_cache_dir", None)
        assert place_compile_cache() == os.path.join(ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == os.path.join(ROOT, ".jax_cache")
        assert jax.config.jax_persistent_cache_min_compile_time_secs <= 0.5
    finally:
        jax.config.update("jax_compilation_cache_dir", saved[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs", saved[1])


def test_plain_invocation_fails_without_a_tpu(runs):
    rc, lines, stderr = runs["plain"]
    assert rc != 0
    assert not any('"ok": true' in line for line in lines)
    assert "platform" in stderr and "not 'tpu'" in stderr
