"""CLI smoke tests — the L7 layer end-to-end (reference legacy/train_dalle.py,
legacy/generate.py): argparse → a few train steps → checkpoint (with embedded
VAE) → generation with no VAE flags, using the shipped CLIP vocab by default.
"""

import importlib.util
import os
import sys

import numpy as np
import pytest

SCRIPTS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                       "scripts")


def _load(name):
    if SCRIPTS not in sys.path:
        sys.path.insert(0, SCRIPTS)
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(SCRIPTS, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def shapes_dir(tmp_path_factory):
    """Tiny image/caption folder via the synthetic rasterizer."""
    out = tmp_path_factory.mktemp("shapes")
    from dalle_tpu.data.synthetic import ShapesDataset
    from PIL import Image
    ds = ShapesDataset(image_size=32)
    for i in range(12):
        sample = ds[i]
        arr = (np.asarray(sample.image) * 255).clip(0, 255).astype("uint8")
        Image.fromarray(arr).save(out / f"s{i:03d}.png")
        (out / f"s{i:03d}.txt").write_text(sample.caption)
    return str(out)


def test_train_checkpoint_generate_roundtrip(shapes_dir, tmp_path):
    """The full reference flow: train 2 steps on folder data with the default
    (49,408-vocab) tokenizer, checkpoint, then generate from --dalle_path
    alone (VAE rebuilt from the checkpoint sidecar)."""
    ckpt = str(tmp_path / "ck")
    outdir = str(tmp_path / "samples")

    train = _load("train_dalle")
    rc = train.main([
        "--image_text_folder", shapes_dir, "--untrained_vae",
        "--image_size", "32", "--untrained_vae_layers", "2",
        "--dim", "32", "--depth", "1", "--heads", "2", "--dim_head", "16",
        "--text_seq_len", "16", "--epochs", "1", "--batch_size", "4",
        "--steps", "2", "--output_dir", ckpt, "--no_preflight"])
    assert rc == 0
    assert os.path.isdir(os.path.join(ckpt, "vae"))

    gen = _load("generate")
    rc = gen.main([
        "--dalle_path", ckpt, "--text", "large red circle|blue square",
        "--num_images", "1", "--batch_size", "1", "--outputs_dir", outdir])
    assert rc == 0
    pngs = [os.path.join(r, f) for r, _, fs in os.walk(outdir)
            for f in fs if f.endswith(".png")]
    assert len(pngs) == 2  # one per prompt
    from PIL import Image
    im = Image.open(pngs[0])
    assert im.size == (32, 32)


def test_generate_rejects_vocab_mismatch(shapes_dir, tmp_path):
    """A checkpoint trained with a small vocab must refuse the default
    49,408-vocab tokenizer instead of silently clipping embedding ids."""
    ckpt = str(tmp_path / "ck_small_vocab")
    train = _load("train_dalle")
    rc = train.main([
        "--image_text_folder", shapes_dir, "--untrained_vae",
        "--image_size", "32", "--untrained_vae_layers", "2",
        "--dim", "32", "--depth", "1", "--heads", "2", "--dim_head", "16",
        "--text_seq_len", "16", "--num_text_tokens", "600",
        "--epochs", "1", "--batch_size", "4", "--steps", "1",
        "--output_dir", ckpt, "--no_preflight"])
    assert rc == 2  # tokenizer vocab 49408 > 600 rejected at train time

    # train with an explicit byte-level-sized vocab via a tiny bpe file
    bpe = tmp_path / "tiny.bpe"
    bpe.write_text("#version: test\nt h\nth e\n")
    rc = train.main([
        "--image_text_folder", shapes_dir, "--untrained_vae",
        "--image_size", "32", "--untrained_vae_layers", "2",
        "--dim", "32", "--depth", "1", "--heads", "2", "--dim_head", "16",
        "--text_seq_len", "16", "--bpe_path", str(bpe),
        "--epochs", "1", "--batch_size", "4", "--steps", "1",
        "--output_dir", ckpt, "--no_preflight"])
    assert rc == 0

    gen = _load("generate")
    rc = gen.main([
        "--dalle_path", ckpt, "--text", "red circle",
        "--num_images", "1", "--batch_size", "1",
        "--outputs_dir", str(tmp_path / "out")])
    assert rc == 2  # default tokenizer vocab exceeds checkpoint's 516

    rc = gen.main([
        "--dalle_path", ckpt, "--text", "red circle", "--bpe_path", str(bpe),
        "--num_images", "1", "--batch_size", "1",
        "--outputs_dir", str(tmp_path / "out")])
    assert rc == 0


def test_train_clip_and_rerank_generation(shapes_dir, tmp_path):
    """CLIP flow end-to-end: train a reranker, then generate with
    --clip_path — scores ordered best-first (reference generate_images
    :553-555; the reference ships no CLIP training script, this framework
    does). CLIP's shorter text context is cropped/padded automatically."""
    dalle_ckpt = str(tmp_path / "dck")
    clip_ckpt = str(tmp_path / "cck")

    train = _load("train_dalle")
    rc = train.main([
        "--image_text_folder", shapes_dir, "--untrained_vae",
        "--image_size", "32", "--untrained_vae_layers", "2",
        "--dim", "32", "--depth", "1", "--heads", "2", "--dim_head", "16",
        "--text_seq_len", "16", "--epochs", "1", "--batch_size", "4",
        "--steps", "1", "--output_dir", dalle_ckpt, "--no_preflight"])
    assert rc == 0

    tclip = _load("train_clip")
    rc = tclip.main([
        "--image_text_folder", shapes_dir, "--image_size", "32",
        "--patch_size", "8", "--dim", "32", "--depth", "1", "--heads", "2",
        "--text_seq_len", "8",  # shorter than DALLE's: exercises crop
        "--epochs", "1", "--batch_size", "4", "--steps", "1",
        "--output_dir", clip_ckpt, "--no_preflight"])
    assert rc == 0

    gen = _load("generate")
    outdir = str(tmp_path / "ranked")
    rc = gen.main([
        "--dalle_path", dalle_ckpt, "--text", "large red circle",
        "--num_images", "2", "--batch_size", "2", "--outputs_dir", outdir,
        "--clip_path", clip_ckpt, "--bf16"])
    assert rc == 0
    pngs = [f for _, _, fs in os.walk(outdir) for f in fs if f.endswith(".png")]
    assert len(pngs) == 2


# -- the one compile-cache rule (utils/misc.enable_compilation_cache) --------

@pytest.fixture
def _restore_cache_config():
    import jax
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    was = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in was.items():
        jax.config.update(k, v)


def test_compile_cache_env_dir_is_left_alone(tmp_path, monkeypatch,
                                             _restore_cache_config):
    import jax
    from dalle_tpu.utils.misc import enable_compilation_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
    got = enable_compilation_cache(str(tmp_path / "flag"))
    assert got == str(tmp_path / "env")
    # no directory is set in code and none is created for the flag
    assert jax.config.jax_compilation_cache_dir == before
    assert not (tmp_path / "flag").exists()
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0


def test_compile_cache_defaults_into_the_checkout(tmp_path, monkeypatch,
                                                  _restore_cache_config):
    import jax
    from dalle_tpu.utils import misc
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert misc.COMPILE_CACHE_DIR == os.path.join(repo, ".xla_cache")
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(misc, "COMPILE_CACHE_DIR", str(tmp_path / "fixed"))
    assert misc.enable_compilation_cache() == str(tmp_path / "fixed")
    assert jax.config.jax_compilation_cache_dir == str(tmp_path / "fixed")
    # an explicit directory wins only when the variable is unset
    assert misc.enable_compilation_cache(str(tmp_path / "flag")) == \
        str(tmp_path / "flag")
