"""Test harness: force an 8-device virtual CPU backend.

This is the fake-distributed-backend the reference lacks entirely (SURVEY §4):
every mesh/pjit/psum/ring-attention test runs against 8 virtual CPU devices,
so multi-chip semantics are exercised without TPU hardware.

Backends initialize lazily, so setting the platform config here (before any
test touches a device) is sufficient whatever `JAX_PLATFORMS` says.
"""

import functools
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

import numpy as np
import pytest
from jax.sharding import Mesh


AXES = ("data", "fsdp", "tensor", "seq", "expert", "pipe")


@pytest.fixture(scope="session")
def mesh8() -> Mesh:
    """2 data x 2 fsdp x 2 tensor mesh over the 8 virtual devices."""
    devs = np.asarray(jax.devices()).reshape(2, 2, 2, 1, 1, 1)
    return Mesh(devs, AXES)


@pytest.fixture(scope="session")
def mesh_seq4() -> Mesh:
    """2 data x 4 seq mesh for ring-attention tests."""
    devs = np.asarray(jax.devices()).reshape(2, 1, 1, 4, 1, 1)
    return Mesh(devs, AXES)


@pytest.fixture(scope="session")
def mesh_exp4() -> Mesh:
    """2 data x 4 expert mesh for MoE expert-parallel tests."""
    devs = np.asarray(jax.devices()).reshape(2, 1, 1, 1, 4, 1)
    return Mesh(devs, AXES)


@pytest.fixture(scope="module", autouse=True)
def _clear_jax_caches_per_module():
    """Bound the XLA CPU client's native-state accumulation.

    A full-suite run compiles many hundreds of executables into ONE
    process; twice (2026-08-02) the run segfaulted INSIDE XLA's
    backend_compile ~430 tests deep (main-thread stack in
    jax/_src/compiler.py backend_compile_and_load — not reproducible on
    any module in isolation, i.e. a native accumulation effect, not a
    test bug). Dropping the compiled-executable caches at each module
    boundary keeps within-module reuse (fixtures' jitted fns stay hot
    across a module's tests) while releasing the native executables of
    every previous module."""
    yield
    jax.clear_caches()


@pytest.fixture(autouse=True)
def _slow_lines_land_in_their_own_test():
    """A slow tick's or a slow turn's WARNING is written by the late-wake
    witness's thread a period or two after the tick (``witness.when_settled``):
    inside the test that made it slow, not in the next one's ``caplog``."""
    yield
    module = sys.modules.get("pretraining_llm_tpu.observability.witness")
    running = getattr(module, "_witness", None)
    deadline = time.monotonic() + 2.0
    while running is not None and running.asked and time.monotonic() < deadline:
        time.sleep(0.005)


@pytest.fixture
def kernel_forced(monkeypatch):
    """``mla.decode_form`` answers as on a TPU, so a few queries a row through
    the latent pool (a decode step's one, a speculative round's ``k + 1``) take
    ``ops/pallas_latent.py`` (interpreted here) and a chunk's many still take
    the gather form. A jitted program keeps the form it was traced with, so
    the caches go before and after."""
    from pretraining_llm_tpu.models import mla

    monkeypatch.setattr(mla, "decode_form", functools.partial(mla.decode_form, backend="tpu"))
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.fixture
def paged_kernel_forced(monkeypatch):
    """``transformer.paged_attention_form`` answers as on a TPU, so the decode
    step over a per-head pool whose pages are copies of their own (heads of
    128) takes ``ops/pallas_paged.py`` (interpreted here); several queries a
    row, int8 pools and narrow heads still take the gather form. Caches as
    above."""
    from pretraining_llm_tpu.models import transformer

    monkeypatch.setattr(
        transformer, "paged_attention_form", functools.partial(transformer.paged_attention_form, backend="tpu")
    )
    jax.clear_caches()
    yield
    jax.clear_caches()
