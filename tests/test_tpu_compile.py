"""Compile the solve path for a described TPU v5e chip, with no chip attached.

Every test lowers and compiles one engine, built as the entry points
build it, for the n = 160,000 Poisson ILU(1) system (``poisson_2d(400)``)
on one chip of a ``v5e:2x2`` topology: what XLA's TPU compiler or Mosaic
would refuse on the chip, these tests refuse here. Nothing runs, so nothing here says
anything about results or times.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU compiler's library, and every pytest
worker imports this file. The persistent compilation cache is off around
these compiles (an entry compiled for a described chip cannot be read back
without one).
"""
import importlib
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core import pilu1_symbolic, poisson_2d
from repro.core.bitmath import hoisted_jit
from repro.core.factor_plan import build_factor_plan
from repro.core.inverse import InversePrecondApply
from repro.core.solvers import csr_to_ell_arrays, gmres_engine, make_ell_matvec
from repro.core.triangular import PrecondApply
from repro.serve.engine import ServeEngine

SIDE = 400  # n = 160,000
RESTART = 30
HBM_BYTES = 16e9  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe means "cannot test here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_cache():
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo, no_cache):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def system():
    """The n = 160,000 Poisson ILU(1) system: matrix, pattern and
    placeholder factor values (a compile needs only the shapes)."""
    a = poisson_2d(SIDE)
    pattern = pilu1_symbolic(a)
    return a, pattern, np.ones(pattern.nnz, np.float32)


def _sds(x, sharding):
    return jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype, sharding=sharding)


def _compile(engine, *args):
    """AOT-compile a solve-path engine (a ``hoisted_jit``) for the described
    chip: the engine object an entry point builds, with its compiler
    options; the arrays it closes over are lowered as runtime operands."""
    compiled = engine.lower(*args).compile().compiled
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes
    assert used < HBM_BYTES, f"{used / 1e9:.1f} GB does not fit one chip"
    return compiled.as_text()


def test_factor_engine_compiles(system, one_chip):
    a, pattern, _ = system
    plan = build_factor_plan(a, pattern)
    text = _compile(plan.engine(), _sds(plan.a_vals, one_chip))
    # the pivot divide is lowered with its TPU repair (integer remainders)
    assert "shift-left" in text


@pytest.mark.parametrize("precond", ["sweep", "inverse"])
def test_gmres_solve_program_compiles(system, one_chip, precond):
    """The engine ``solve_with_ilu`` runs: GMRES over the ELL matvec and
    the factorization's preconditioner apply."""
    a, pattern, vals = system
    matvec = make_ell_matvec(*csr_to_ell_arrays(a), a.n)
    M = PrecondApply(pattern, vals) if precond == "sweep" else InversePrecondApply(pattern, vals)
    engine = gmres_engine(matvec, M, RESTART, 1e-5, 20)
    text = _compile(engine, jax.ShapeDtypeStruct((a.n,), jnp.float32, sharding=one_chip))
    # every solve-path op is an XLA op: no Pallas kernel is left on it
    assert "tpu_custom_call" not in text


def test_serve_bucket_program_compiles(system, one_chip):
    """The service's vmapped bucket program (bucket 8, sweep), as
    ``ServeEngine`` compiles it for a bound value version."""
    a, pattern, vals = system
    eng = ServeEngine(a, pattern, vals, restart=RESTART, maxiter=20, buckets=(8,))
    vargs = jax.tree.map(lambda v: _sds(v, one_chip), eng.bind(a, vals).value_args)
    bs = jax.ShapeDtypeStruct((8, a.n), jnp.float32, sharding=one_chip)
    tols = jax.ShapeDtypeStruct((8,), jnp.float32, sharding=one_chip)
    _compile(eng._jit, vargs, bs, tols)


def test_panel_update_kernel_compiles(one_chip):
    """The BILU panel GEMM, the Pallas kernel Mosaic accepts, compiles
    as a TPU custom call."""
    pu = importlib.import_module("repro.kernels.panel_update")

    def kernel(c, a, b):
        return pu.panel_update(c, a, b, bm=256, bn=256, bk=128, interpret=False)

    shapes = ((512, 512), (512, 256), (256, 512))
    text = _compile(hoisted_jit(kernel), *(jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
                                           for s in shapes))
    assert "tpu_custom_call" in text
