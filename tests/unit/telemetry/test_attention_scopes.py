"""The attention block's four scopes (ISSUE 56) in the LOWERED text of the two
training models' loss, forward and under ``jax.grad`` (``jit(...).lower(...)``
on shapes: nothing is compiled, nothing runs), and the parameter tree's paths,
which a ``jax.named_scope`` must leave as they were."""

import re

import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.models import llama, smallthinker
from deepspeed_tpu.telemetry import names

SCOPES = (names.SCOPE_ATTN_PROJ, names.SCOPE_ATTN_ROTARY,
          names.SCOPE_ATTN_KV_REPEAT, names.SCOPE_ATTN_CORE)
#: what each scope holds: a flax module or a primitive under it
HOLDS = {names.SCOPE_ATTN_PROJ: ("q_proj", "k_proj", "v_proj", "o_proj"),
         names.SCOPE_ATTN_ROTARY: ("mul", ),
         names.SCOPE_ATTN_KV_REPEAT: ("broadcast_in_dim", ),
         names.SCOPE_ATTN_CORE: ("dot_general", )}
IDS = jnp.zeros((1, 16), jnp.int32)


def _llama():
    return llama.LlamaModel(llama.llama_tiny(dtype="float32", remat=True))


def _smallthinker():
    return smallthinker.SmallThinkerModel(
        smallthinker.smallthinker_tiny(dtype="float32"))


MODELS = {"llama": _llama, "smallthinker": _smallthinker}


@pytest.fixture(scope="module", params=sorted(MODELS))
def lowered(request):
    """``(parameter paths, forward op paths, backward op paths)``."""
    model = MODELS[request.param]()
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), IDS)["params"])

    def loss(p):
        out = model.apply({"params": p}, IDS, IDS)
        return out[0] if isinstance(out, tuple) else out

    def op_paths(fn):
        text = jax.jit(fn).lower(shapes).as_text(debug_info=True)
        return set(re.findall(r'loc\("([^"]*)"', text))

    params = ["/".join(str(k.key) for k in path) for path, _ in
              jax.tree_util.tree_flatten_with_path(shapes)[0]]
    return params, op_paths(loss), op_paths(jax.grad(loss))


@pytest.mark.parametrize("scope", SCOPES)
def test_a_scope_stands_in_the_forward_and_the_backward_pass(lowered, scope):
    _, forward, backward = lowered

    def under(paths, transposed):
        return [p for p in paths if f"/{names.MODULE_ATTENTION}/{scope}/" in p
                and (names.MARK_TRANSPOSE in p) == transposed
                and any(f"/{held}" in p for held in HOLDS[scope])]

    assert under(forward, False), scope
    assert under(backward, True), scope
    # the flax module stays in the path: the class ``attention`` is unmoved
    assert not [p for p in forward | backward if f"/{scope}/" in p
                and f"/{names.MODULE_ATTENTION}/" not in p]


def test_the_four_scopes_do_not_nest(lowered):
    _, forward, backward = lowered
    for p in forward | backward:
        assert sum(f"/{s}/" in p for s in SCOPES) <= 1, p


def test_a_named_scope_renames_no_parameter(lowered):
    params, _, _ = lowered
    assert not [p for p in params if "ds." in p]
    attention = sorted(p for p in params
                       if p.startswith("layers_0/" + names.MODULE_ATTENTION))
    assert attention == [
        f"layers_0/{names.MODULE_ATTENTION}/{w}_proj/kernel"
        for w in "koqv"]
