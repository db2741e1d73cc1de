"""The block's new kinds (PR 32: ``attention_layers``, ``gqa_gated``,
``kda``, ``positions: none``, the router's score and normalisation; PR 34:
the decay's and beta's forms, whole gate projections, latent attention's
direct queries, head norms, gate and tier, ``noaux_tc`` routing, the
multi-token-prediction block) left the accepted configurations as they
were: at the benchmark tests' tiny sizes, each one's parameter tree leaf for
leaf and its lowered train step character for character are those of the
commit before (the first three: 98e0bf9, before PR 32; ``tiny_solar2_config``:
1b46002, before PR 34, its step since PR 37, whose linear layers run one scan
and are rematerialised with ``KDA_SAVED``), by their digests. At the
configurations' own sizes the same comparison, parent against change, is in
CHANGES.md.

A later PR that changes the default block's program on purpose regenerates
the digests: run this file with ``-s`` and copy what it prints.
"""

import hashlib
import json
import os

import jax
import jax.numpy as jnp
import pytest

from dalle_tpu.config import DalleConfig, OptimConfig
from dalle_tpu.models.dalle import DALLE
from dalle_tpu.train.train_state import TrainState, make_optimizer
from dalle_tpu.train.trainer_dalle import _dalle_step_body

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "benchmarks",
                    "data")
# configuration (its block), optimizer, (tree, lowered step) at 98e0bf9
CASES = {
    "tiny_config": ("adam", ('613a4e4d4adc6518', '66773f640a24d88a')),
    "mid_config": ("adafactor", ('753ede710630dd0d', 'ea3b75f8da758146')),
    "tiny_dsv2_config": ("adafactor", ('227095e1baf655aa', '971e1eeab280ea8f')),
    "tiny_solar2_config": ("adafactor", ('4bababd7339b4602', '3fa22daea60fafc8')),
}


def digests(name: str, optimizer: str) -> tuple:
    with open(os.path.join(DATA, f"{name}.json")) as f:
        cfg = DalleConfig(**json.load(f)["model"])
    model = DALLE(cfg)
    text = jnp.zeros((2, cfg.text_seq_len), jnp.int32)
    ids = jnp.zeros((2, cfg.image_seq_len), jnp.int32)
    key = jax.random.PRNGKey(0)
    params = jax.eval_shape(
        lambda k: model.init({"params": k, "cfg": k}, text[:1], ids[:1],
                             return_loss=True), key)
    tree = json.dumps([(jax.tree_util.keystr(path), list(x.shape),
                        str(x.dtype))
                       for path, x in
                       jax.tree_util.tree_leaves_with_path(params)])
    tx = make_optimizer(OptimConfig(optimizer=optimizer, learning_rate=3e-4,
                                    grad_clip_norm=0.5))
    state = jax.eval_shape(lambda p: TrainState.create(
        apply_fn=model.apply, params=p, tx=tx, lr_scale=None), params)
    step = jax.jit(_dalle_step_body(model, dtype=jnp.bfloat16),
                   donate_argnums=(0,)).lower(state, text, ids, key).as_text()
    return tuple(hashlib.sha256(t.encode()).hexdigest()[:16]
                 for t in (tree, step))


@pytest.mark.parametrize("name", sorted(CASES))
def test_a_configuration_keeps_its_tree_and_its_lowered_step(name):
    optimizer, want = CASES[name]
    got = digests(name, optimizer)
    print(f'\n    "{name}": ("{optimizer}", {got!r}),')
    assert got[0] == want[0], "the parameter tree changed"
    assert got[1] == want[1], "the lowered train step changed"
