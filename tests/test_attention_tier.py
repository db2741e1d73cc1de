"""The one chooser of the training-attention tier
(``ops.attention.attention_tier``): its table, what it answers for the
benchmark's own configurations on the TPU, and the values it no longer takes.

The cells' files are read, never edited. On the chip every benchmark run
counts the Mosaic calls of its lowered step (36 for ``train_small_b64``, 48
for ``train_dsv2_share16_fit``, all of the latter grouped products); the
cell cases here are that check's twin on the CPU: which tier each stack is
built with when the backend says "tpu".
"""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from dalle_tpu.config import DalleConfig
from dalle_tpu.models.latent_moe import MLAttention
from dalle_tpu.models.transformer import Attention, Transformer
from dalle_tpu.ops.attention import attention_tier

CONFIGS = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                       "configs")


@pytest.mark.parametrize("use_pallas, seq_len, heads, dim_head, backend, tier", [
    ("auto", 512, 8, 64, "tpu", "fused"),      # dalle_small's shape
    ("auto", 513, 16, 64, "tpu", "fused"),     # under the raised 32 MB ceiling
    ("auto", 513, 14, 128, "tpu", "dense"),    # its backward does not fit
    ("auto", 1152, 16, 128, "tpu", "dense"),   # the flagship
    ("auto", 1280, 8, 64, "tpu", "dense"),     # small at the README's grid
    ("auto", 2048, 8, 64, "tpu", "flash"),
    ("auto", 4352, 8, 64, "tpu", "flash"),
    ("auto", 512, 8, 64, "cpu", "dense"),
    ("auto", 4352, 8, 64, "cpu", "dense"),
    ("off", 4352, 8, 64, "tpu", "dense"),
    (False, 4352, 8, 64, "tpu", "dense"),
])
def test_the_rule(use_pallas, seq_len, heads, dim_head, backend, tier):
    assert attention_tier(use_pallas, seq_len, heads, dim_head,
                          backend=backend) == tier


@pytest.mark.parametrize("retired", ["on", "fused", "persist", True])
def test_retired_values_are_refused_by_name(retired):
    with pytest.raises(ValueError, match=r'"auto" or "off"'):
        attention_tier(retired, 512, 8, 64, backend="tpu")


def cell_config(name: str, **over) -> DalleConfig:
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return DalleConfig(**{**json.load(f)["model"], **over})


@pytest.mark.parametrize("name, tier", [("dalle_small", "fused"),
                                        ("rudalle_malevich", "dense")])
def test_a_dense_cells_stack_on_the_tpu(monkeypatch, name, tier):
    """The chooser is asked once, with the configured length, and every
    attention layer of the stack carries its answer."""
    cfg = cell_config(name)
    asked = []

    def on_the_tpu(*args):
        asked.append(args)
        return attention_tier(*args, backend="tpu")
    monkeypatch.setattr("dalle_tpu.models.transformer.attention_tier",
                        on_the_tpu)
    stack = Transformer(cfg.transformer()).bind({})
    assert len(stack.attn_layers) == cfg.depth     # setup runs here
    assert all(type(layer.fn) is Attention and layer.fn.tier == tier
               for layer in stack.attn_layers)
    assert asked == [(cfg.use_pallas, cfg.total_seq_len, cfg.heads,
                      cfg.dim_head)]


def test_the_latent_cells_stack_is_built_without_a_tier(monkeypatch):
    """``deepseek_v2_share16`` at its own widths (depth cut to 1, shapes
    only), built for the TPU: latent attention asks like every softmax
    layer (PR 34), and whatever the chooser answers at 1280 positions, under
    ``FLASH_MIN_SEQ``, the layer is the dense tier it was."""
    from dalle_tpu.models import transformer
    chooser, answers = transformer.attention_tier, []

    def on_tpu(*args, **kw):
        answers.append(chooser(*args, backend="tpu"))
        return answers[-1]
    monkeypatch.setattr("dalle_tpu.models.transformer.attention_tier", on_tpu)
    cfg = cell_config("deepseek_v2_share16", depth=1)
    stack = Transformer(cfg.transformer())
    x = jax.ShapeDtypeStruct((1, cfg.total_seq_len, cfg.dim), jnp.float32)
    shapes = jax.eval_shape(stack.init, jax.random.PRNGKey(0), x)["params"]
    assert {"q_a", "q_b", "kv_a", "kv_b"} <= set(shapes["attn_0"])
    bound = stack.bind({})
    assert [type(layer.fn) for layer in bound.attn_layers] == [MLAttention]
    assert answers and "flash" not in answers
    assert [layer.fn.tier for layer in bound.attn_layers] == ["dense"]
    assert transformer.stack_layers(cfg.transformer())["tier"] == "dense"
