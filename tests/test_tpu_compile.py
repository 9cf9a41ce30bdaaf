"""Compiles for a described TPU v5e chip, which need not be attached.

The TPU compiler refuses what the CPU backend and the Pallas interpreter
accept: blocks off Mosaic's (8, 128) tiling, programs larger than the
chip's HBM.  These tests compile, without running:

  - the Pallas kernels at Llama-3.2-1B widths (d_model 2048, d_ff 8192,
    32 heads of 64), each lowered through Mosaic (``interpret=False``);
  - the batched LLM stage's round program at those widths over the
    README quickstart task, within one chip's HBM;
  - the fused round program at 4 qubits with 5 clients, whose tape
    replays hold no loop.

The two programs' compiled texts also carry the program's layer scopes
(``repro.telemetry``), as the chip's compiler leaves them.

The topology is described inside a module fixture, never while a module
is imported: one process at a time may load the TPU library, and under
pytest-xdist every worker imports every test file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from hlo_scopes import scopes, unscoped_share
from repro import telemetry as tel
from repro.core import llm_client as llmc
from repro.core.batched_llm import get_llm_round_fn
from repro.core.fused_rounds import FusedRoundDriver
from repro.data.tasks import build_task
from repro.kernels import distill_kl, flash_attention, int4_matmul
from repro.kernels import lora_matmul
from repro.models import model as M
from repro.optim import adamw
from repro.quantum import backends as backend_mod
from repro.quantum import qnn

HBM_BYTES = 15.75e9          # the HBM XLA allots one v5e chip's program
D, FF, H, HD = 2048, 8192, 32, 64
TOKENS = 1024


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU library here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def task():
    return build_task("genomic", n_clients=5, train_size=250,
                      test_size=100, val_size=60, seed=0)


def _on(sharding, tree):
    """Abstract stand-ins of ``tree``'s leaves, placed on ``sharding``."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


KERNELS = {
    "lora_matmul": (
        lambda x, w, a, b: lora_matmul.lora_matmul(x, w, a, b, scale=2.0,
                                                   interpret=False),
        [_sds((TOKENS, D), jnp.float32), _sds((D, FF), jnp.float32),
         _sds((D, 8), jnp.float32), _sds((8, FF), jnp.float32)]),
    "flash_attention": (
        lambda q, k, v: flash_attention.flash_attention(q, k, v,
                                                        interpret=False),
        [_sds((2, H, 1024, HD), jnp.float32)] * 3),
    "int4_matmul": (
        lambda x, p, s: int4_matmul.int4_matmul(x, p, s, qblock=64,
                                                interpret=False),
        [_sds((TOKENS, D), jnp.float32), _sds((D, FF // 2), jnp.uint8),
         _sds((D, FF // 64), jnp.float32)]),
    "distill_kl": (
        lambda t, z: distill_kl.distill_kl(t, z, interpret=False),
        [_sds((4096, 2), jnp.float32)] * 2),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_through_mosaic(one_chip, name):
    fn, args = KERNELS[name]
    compiled = jax.jit(fn).lower(*_on(one_chip, args)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_llm_stage_fits_one_chip(one_chip, task):
    """The batched LLM stage at Llama-3.2-1B widths (f32 base, 5 clients
    × 50 examples, L=64, batch 16): per-layer remat keeps the saved
    activations of the vmapped clients inside one chip's HBM."""
    cfg = llmc.task_llm_config("llama3.2-1b", task.vocab_size,
                               task.llm_seq_len)
    C = task.n_clients
    n, L = max(cl.n for cl in task.clients), task.llm_seq_len

    def init():
        base = M.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
        adapters = jax.vmap(lambda k: M.init_adapters(cfg, k, base))(
            jax.random.split(jax.random.PRNGKey(1), C))
        return (base, adapters, jax.vmap(adamw.init)(adapters),
                jax.random.split(jax.random.PRNGKey(2), C))

    base, adapters, opt, ckeys = jax.eval_shape(init)
    fn = get_llm_round_fn(cfg, n_labels=task.n_classes, lr=3e-3,
                          batch_size=16, steps=2, rho=0.25)
    args = (base, adapters, opt, _sds((C, n, L), jnp.int32),
            _sds((C, n, L), jnp.int32), _sds((C, n), jnp.float32),
            _sds((C,), jnp.int32), _sds((C,), jnp.float32), ckeys,
            _sds((), jnp.int32))
    compiled = fn.lower(*_on(one_chip, args)).compile()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert mem.argument_size_in_bytes > 3.5e9     # the 1B f32 base is in
    assert used < HBM_BYTES, (mem.argument_size_in_bytes,
                              mem.temp_size_in_bytes)
    text = compiled.as_text()
    assert set(tel.LLM_SCOPES) <= scopes(text)
    assert unscoped_share(text) < 0.1


def test_fused_rounds_compile(one_chip, task):
    """The fused llm-qfl round loop (Nelder–Mead, regulation up to the
    budget cap) at 4 qubits over the 5-client task."""
    spec = qnn.QNNSpec("vqc", n_qubits=4, n_classes=task.n_classes)
    teacher = [np.full((cl.n, task.n_classes), 1.0 / task.n_classes)
               for cl in task.clients]
    driver = FusedRoundDriver(
        task, spec, backend_mod.get("exact"), optimizer="nelder-mead",
        use_llm=True, teacher_probs=teacher,
        llm_losses=[0.5] * task.n_clients, n_rounds=3)
    args = driver.program_args(np.zeros(spec.n_params))
    compiled = driver.program.lower(*_on(one_chip, args)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < HBM_BYTES
    # every client selected: no selection, no cohort to gather
    text = compiled.as_text()
    assert set(tel.ROUND_SCOPES) - {tel.QFL_SELECT, tel.QFL_GATHER} \
        <= scopes(text)
    assert unscoped_share(text) < 0.1
    # the tape replays as straight-line code: no loop under its scope
    assert not [line for line in text.splitlines()
                if " while(" in line and tel.TAPE_REPLAY in line]
