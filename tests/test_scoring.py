"""What the scoring forward (``nn/scoring.py``) does alike for the five models, each at its
test file's tiny configuration: the dtypes it holds and returns with x64 on, and the
documents it refuses."""

import pytest

import jax
import jax.numpy as jnp

import heat_tpu as ht

import test_deepseek_v32
import test_kimi_linear
import test_ling
import test_trinity
import test_xing4

MODELS = {
    "xing4": (ht.nn.Xing4, test_xing4),
    "trinity": (ht.nn.Trinity, test_trinity),
    "ling": (ht.nn.Ling, test_ling),
    "dsv32": (ht.nn.DeepseekV32, test_deepseek_v32),
    "kimi": (ht.nn.KimiLinear, test_kimi_linear),
}


def model_of(name: str):
    """The model in bfloat16, its abstract weights and its test file's document length."""
    cls, tests = MODELS[name]
    model = cls(tests.CFG, continuation=tests.CONT, dtype=jnp.bfloat16, block_rows=16)
    return model, jax.eval_shape(model.init, jax.random.key(19)), tests.T


@pytest.mark.parametrize("name", list(MODELS))
def test_dtypes_are_pinned_under_x64(name):
    """The framework enables x64 globally; nothing here may widen to float64 / int64."""
    model, params, t = model_of(name)
    assert {str(leaf.dtype) for leaf in jax.tree_util.tree_leaves(params)} == {"bfloat16",
                                                                             "float32"}
    out = jax.eval_shape(model._forward, params, jax.ShapeDtypeStruct((t,), jnp.int32))
    assert {str(leaf.dtype) for leaf in out} == {"float32", "int32"}


@pytest.mark.parametrize("shape", ["rank 2", "too short"])
@pytest.mark.parametrize("name", list(MODELS))
def test_a_badly_shaped_document_is_refused_in_words(name, shape):
    """One document of shape (T,) with T >= continuation + ahead + 1: a batch of documents, or
    one that leaves no position before the first scored one, is refused by the class's name
    before the program is built."""
    model, params, t = model_of(name)
    tokens = (jnp.zeros((2, t), jnp.int32) if shape == "rank 2"
              else jnp.zeros((model.continuation + model.ahead,), jnp.int32))
    with pytest.raises(ValueError, match=f"{type(model).__name__} scores one document"):
        model.apply(params, tokens)
    assert "_program" not in vars(model)
