"""The names the device trace shows: the serve step programs carry the
KV data-movement scopes (``kv_write``, ``kv_gather``), the kernel's
``paged_decode`` scope and the ``mlp`` / ``lm_head`` scopes in their
op locations, which become the ops' metadata, and the paged decode
kernel its ``pallas_call`` name.  A profile reduction finds the work
by these names, so a refactor that drops one fails here.  (``kv_read``
holds ops only where the pool's width or dtype differs from the
kernel's operands; here it does not.)"""
import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import init_lm_params
from repro.models import transformer as T

B, PT, N_PAGES, TABLE = 2, 4, 9, 4


@pytest.fixture(scope="module")
def lowered():
    """Lowered text of the paged chunk and decode steps (Pallas kernels
    in interpret mode), with debug locations."""
    cfg = dataclasses.replace(get_config("musicgen-large").reduced(),
                              kernel_impl="interpret")
    params = init_lm_params(cfg, jax.random.PRNGKey(0))
    state = T.init_decode_state_paged(cfg, B, N_PAGES, PT)
    state["index"] = jnp.zeros((B,), jnp.int32)
    pages = jnp.asarray(np.arange(B * TABLE).reshape(B, TABLE), jnp.int32)
    chunk = jax.jit(functools.partial(T.prefill_chunk, cfg=cfg)).lower(
        params, tokens=jnp.zeros((B, 4), jnp.int32), state=state,
        lengths=jnp.full((B,), 4, jnp.int32), pages=pages)
    decode = jax.jit(functools.partial(T.decode_step, cfg=cfg)).lower(
        params, token=jnp.zeros((B,), jnp.int32), state=state,
        pages=pages)
    return {"chunk": chunk.as_text(debug_info=True),
            "decode": decode.as_text(debug_info=True)}


@pytest.mark.parametrize("program,scope", [
    ("chunk", "kv_write"), ("chunk", "kv_gather"), ("chunk", "mlp"),
    ("chunk", "lm_head"), ("decode", "kv_write"),
    ("decode", "paged_decode"), ("decode", "mlp"), ("decode", "lm_head")])
def test_step_programs_carry_their_scopes(lowered, program, scope):
    assert _in_scope(scope, lowered[program])


def _in_scope(scope, text):
    """Some op location names ``scope`` as a path component."""
    return re.search(rf'loc\("(?:[^"]*/)?{scope}/', text) is not None


def test_each_step_program_keeps_to_its_own_kv_path(lowered):
    assert not _in_scope("paged_decode", lowered["chunk"])
    assert not _in_scope("kv_gather", lowered["decode"])
    # the kernel keeps its wrapper's name inside the decode scope
    assert _in_scope("paged_decode/paged_flash_decode", lowered["decode"])
