"""DALL·E — the autoregressive text→image transformer.

Reference: ``DALLE`` (dalle_pytorch/dalle_pytorch.py:336-653). Capability parity:
per-position unique padding tokens (:370,578-579), <bos> prepend (:583), combined
text+image vocab with the static logits mask (:428-439), 7:1 image loss weighting
(:440,649-653), classifier-free-guidance text dropout (:570-574), stable-training
tricks (token blend :615-617 + DivideMax), shared input/output embeddings
(:71-83,421-423), axial positional embeddings when rotary is off, incremental
decoding with caches, top-k+gumbel sampling, image priming, text generation.

TPU redesign:
  * The VAE is NOT a submodule. JAX has no "frozen submodule" notion worth
    carrying; the model consumes image *token ids* and a thin ``DalleWithVae``
    wrapper tokenizes raw pixels through any VAE adapter (reference freezes the
    vae inside the module, :386-387 — same capability, cleaner separation).
  * ``generate_images`` is a single ``lax.scan`` over a preallocated cache
    pytree: O(1) compilations, static shapes, runs entirely on-device.
  * CFG keeps TWO caches (conditioned + null-text). The reference's cached CFG
    forks the *conditioned* cache for the null pass every step
    (dalle_pytorch.py:528-538), so its null branch silently attends to
    conditioned text keys; this implements the semantics its uncached path
    (use_cache=False) defines. Not a copy — a fix.
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax

from ..config import DalleConfig
from ..ops.quantize_weights import QDense
from ..ops.sampling import (gumbel_sample, gumbel_sample_rows,
                            prob_mask_like, top_k_filter)
from ..ops.table_lookup import grad_path, take_rows
from .latent_moe import RMSNorm
from .transformer import DivideMax, Transformer, reduce_counters

MASK_VALUE = -1e9  # max_neg/2-style fill for the logits mask


class AxialPositionalEmbedding(nn.Module):
    """Learned factored 2D position embedding: row + col tables broadcast over
    the grid and summed (reference axial_positional_embedding.py:6-74, used with
    full-dim per axis as DALLE does)."""
    dim: int
    shape: Tuple[int, int]

    def setup(self):
        h, w = self.shape
        init = nn.initializers.normal(stddev=1.0)
        self.row = self.param("row", init, (h, 1, self.dim))
        self.col = self.param("col", init, (1, w, self.dim))

    def __call__(self, n: Optional[int] = None):
        h, w = self.shape
        emb = (self.row + self.col).reshape(h * w, self.dim)
        return emb if n is None else emb[:n]


def loss_segments(cfg: DalleConfig, chunk: int, shift: int = 0):
    """The training loss's head as ``[((r0, r1), (c0, c1))]``, in order: the
    sequence cut every ``chunk`` positions (0: not at all) and at
    ``text_seq_len``, so each row window holds text positions only or image
    positions only, beside the columns of the head the logits mask allows
    there: the text vocabulary's (per-position pads included) or the
    codebook's. ``shift``: position ``i`` predicts the label of position
    ``i + shift`` (the multi-token-prediction pass: 1), so there are
    ``shift`` fewer rows and the boundary sits ``shift`` positions
    earlier."""
    n = cfg.total_seq_len - shift
    text, text_cols = (cfg.text_seq_len - shift,
                       cfg.total_tokens - cfg.image_vocab_size)
    edges = sorted({*range(0, n, chunk or n), text, n})
    return [((r0, r1), (0, text_cols) if r1 <= text
             else (text_cols, cfg.total_tokens))
            for r0, r1 in zip(edges, edges[1:])]


def _ce_segment(mdl, x, labels, kernel, bias):
    """Head + cross-entropy of one segment against its own vocabulary's
    columns of the head; ``labels`` count from the first of them.
    Module-first so ``nn.remat`` can lift it (same pattern as
    transformer._block_body)."""
    if mdl.cfg.stable:
        x = mdl.norm_by_max(x)
    logits = mdl.final_norm(x) @ kernel + bias
    return optax.softmax_cross_entropy_with_integer_labels(
        logits.astype(jnp.float32), labels)


def _ce_segment_mtp(mdl, x, labels, kernel, bias):
    """``_ce_segment`` behind the multi-token-prediction block's own final
    norm (the head's two leaves are the main pass's)."""
    logits = mdl.mtp_final_norm(x) @ kernel + bias
    return optax.softmax_cross_entropy_with_integer_labels(
        logits.astype(jnp.float32), labels)


def _mtp_merge(mdl, hidden, tokens):
    """h'_i = W_eh [RMSNorm(h_i) ; RMSNorm(e_{i+1})] for i = 0 .. n - 2:
    the stack's output before the final norm beside the embedded input one
    position on. Module-first for ``nn.remat``."""
    return mdl.mtp_merge(jnp.concatenate(
        [mdl.mtp_norm_h(hidden[:, :-1]), mdl.mtp_norm_e(tokens[:, 1:])],
        axis=-1))


class DALLE(nn.Module):
    cfg: DalleConfig
    # sequence-parallel mesh: routes the training forward's attention through
    # ring attention over the 'sp' axis (static module metadata; generation
    # paths keep the cached dense core)
    sp_mesh: Any = None

    def setup(self):
        c = self.cfg
        self.num_text_tokens = c.num_text_tokens + c.text_seq_len  # + per-pos pads
        self.total_tokens = self.num_text_tokens + c.image_vocab_size
        self.transformer = Transformer(c.transformer(), sp_mesh=self.sp_mesh,
                                       name="transformer")

        if c.share_input_output_emb:
            # one (total_tokens, dim) table serves both embeddings and the
            # output projection (reference SharedEmbedding, :71-83)
            self.shared_emb = self.param(
                "shared_emb", nn.initializers.normal(stddev=0.02),
                (self.total_tokens, c.dim))
            self.logits_bias = self.param(
                "logits_bias", nn.initializers.zeros, (self.total_tokens,))
        else:
            self.text_emb = nn.Embed(self.num_text_tokens, c.dim, name="text_emb")
            self.image_emb = nn.Embed(c.image_vocab_size, c.dim, name="image_emb")
            self.head = QDense(self.total_tokens, name="to_logits")

        if not c.rotary_emb:
            self.text_pos_emb = nn.Embed(c.text_seq_len + 1, c.dim,
                                         name="text_pos_emb")
            self.image_pos_emb = AxialPositionalEmbedding(
                c.dim, (c.image_fmap_size, c.image_fmap_size),
                name="image_pos_emb")

        self.final_norm = (
            RMSNorm(c.block.rms_norm_eps, name="final_norm")
            if c.block.norm == "rmsnorm" else nn.LayerNorm(name="final_norm"))
        self.norm_by_max = DivideMax(axis=-1)
        if c.mtp_depth:
            # one more latent-attention + routed layer behind the stack
            # (DeepSeek-V3, arXiv:2412.19437 section 2.2), built as a stack
            # of depth 1: its tier, rotary table, remat and counters are
            # ``Transformer``'s
            eps = c.block.rms_norm_eps
            self.mtp_norm_h = RMSNorm(eps, name="mtp_norm_h")
            self.mtp_norm_e = RMSNorm(eps, name="mtp_norm_e")
            self.mtp_merge = nn.Dense(c.dim, use_bias=False, name="mtp_merge")
            self.mtp_block = Transformer(c.mtp_transformer(),
                                         name="mtp_block")
            self.mtp_final_norm = RMSNorm(eps, name="mtp_final_norm")

        # static (seq, total_tokens) allow-mask: text positions predict text
        # tokens, image positions image tokens (reference :428-439, inverted
        # polarity: here True = allowed)
        seq_range = np.arange(c.total_seq_len)[:, None]
        logit_range = np.arange(self.total_tokens)[None, :]
        forbidden = (((seq_range >= c.text_seq_len) & (logit_range < self.num_text_tokens)) |
                     ((seq_range < c.text_seq_len) & (logit_range >= self.num_text_tokens)))
        self.logits_allow = jnp.asarray(~forbidden)

    # -- embedding helpers -------------------------------------------------
    def _shared_rows(self, ids):
        """Gather from the tied table; int8 tables (decode weight quant,
        ops/quantize_weights.py) dequantize per gathered row — only the int8
        bytes cross HBM."""
        tab = self.shared_emb
        rows = take_rows(tab, ids)
        if tab.dtype == jnp.int8:
            scale = self.get_variable("quant", "shared_emb_scale")
            dt = self.logits_bias.dtype
            rows = rows.astype(dt) * jnp.take(scale, ids, axis=0).astype(dt)
        return rows

    def _embed_text_ids(self, ids):
        if self.cfg.share_input_output_emb:
            return self._shared_rows(ids)
        return take_rows(self.text_emb.embedding, ids)

    def _embed_image_ids(self, ids):
        if self.cfg.share_input_output_emb:
            return self._shared_rows(ids + self.num_text_tokens)
        return take_rows(self.image_emb.embedding, ids)

    def _logits(self, x):
        x = self.final_norm(x)
        if self.cfg.share_input_output_emb:
            tab = self.shared_emb
            if tab.dtype == jnp.int8:
                scale = self.get_variable("quant", "shared_emb_scale")
                tab = tab.astype(x.dtype) * scale.astype(x.dtype)
            return x @ tab.T + self.logits_bias
        return self.head(x)

    def remap_and_bos(self, text):
        """0-pads → unique per-position pad ids; prepend <bos>=0
        (reference :578-583). Text longer than text_seq_len is cropped, shorter
        is 0-padded (reference generate_images crops at :507; tokenizers pad)."""
        c = self.cfg
        n = text.shape[1]
        if n > c.text_seq_len:
            text = text[:, :c.text_seq_len]
        elif n < c.text_seq_len:
            text = jnp.pad(text, ((0, 0), (0, c.text_seq_len - n)))
        pad_ids = jnp.arange(c.text_seq_len) + c.num_text_tokens
        text = jnp.where(text == 0, pad_ids[None, :], text)
        return jnp.pad(text, ((0, 0), (1, 0)))  # <bos> id 0

    def embed_text(self, text_with_bos):
        n = text_with_bos.shape[1]
        tok = self._embed_text_ids(text_with_bos)
        if not self.cfg.rotary_emb:
            tok = tok + self.text_pos_emb(jnp.arange(n))
        return tok

    def embed_image(self, image_ids, first_pos: int = 0):
        tok = self._embed_image_ids(image_ids)
        if not self.cfg.rotary_emb:
            n = image_ids.shape[1]
            tok = tok + self.image_pos_emb()[first_pos:first_pos + n]
        return tok

    def _head_leaves(self):
        """The head's two leaves as ``(kernel (dim, total_tokens), bias)``."""
        if self.cfg.share_input_output_emb:
            return self.shared_emb.T, self.logits_bias
        if self.is_initializing():
            self.head(jnp.zeros((1, self.cfg.dim)))   # declares the leaves
        leaves = self.head.variables["params"]
        return leaves["kernel"], leaves["bias"]

    def _stabilize(self, tokens):
        if self.cfg.stable:  # α-blend trick (reference :615-617)
            alpha = 0.1
            tokens = tokens * alpha + jax.lax.stop_gradient(tokens) * (1 - alpha)
        return tokens

    def _finish(self, x, mask_rows):
        """transformer output → masked logits. ``mask_rows``: (start, n) row
        window of the static logits mask aligned with these positions."""
        if self.cfg.stable:
            x = self.norm_by_max(x)
        logits = self._logits(x)
        start, n = mask_rows
        allow = jax.lax.dynamic_slice_in_dim(self.logits_allow, start, n, axis=0)
        return jnp.where(allow[None], logits, MASK_VALUE)

    # -- training forward --------------------------------------------------
    def __call__(self, text, image_ids, return_loss: bool = False,
                 null_cond_prob: float = 0.0, deterministic: bool = True):
        """``text``: (b, text_seq_len) int32 (0 = pad); ``image_ids``:
        (b, image_seq_len) int32 codebook indices."""
        c = self.cfg
        assert text.shape[1] == c.text_seq_len, (
            f"text must be {c.text_seq_len} tokens, got {text.shape[1]}")

        if null_cond_prob > 0:
            # CFG dropout: whole-row text nulling (reference :570-574)
            null = prob_mask_like(self.make_rng("cfg"), (text.shape[0],),
                                  null_cond_prob)
            text = jnp.where(null[:, None], 0, text)

        text_b = self.remap_and_bos(text)
        tokens = jnp.concatenate(
            [self.embed_text(text_b), self.embed_image(image_ids)], axis=1)
        # drop final token when over length (reference :608-613)
        if tokens.shape[1] > c.total_seq_len:
            tokens = tokens[:, :c.total_seq_len]
        tokens = self._stabilize(tokens)

        # counters of the layers that count (routed experts): {} otherwise
        out, counters = self.transformer(tokens, deterministic=deterministic,
                                         return_aux=True)

        if not return_loss:
            return self._finish(out, (0, tokens.shape[1]))

        # each position's label within its own vocabulary (the logits mask
        # lets a text position predict text tokens only, an image position
        # codes only: the loss never builds the columns it would forbid)
        labels = jnp.concatenate([text_b[:, 1:], image_ids], axis=1)
        n = tokens.shape[1]
        if c.loss_chunk > 0 and n % c.loss_chunk != 0:
            raise ValueError(
                f"loss_chunk={c.loss_chunk} must divide the sequence length "
                f"{n} — a silent fall-back would rematerialize the full "
                f"(b, n, vocab) logits the option exists to avoid")
        with jax.named_scope("loss"):   # the vocabulary head and the CE
            # chunked head+CE under remat: a segment's logits are recomputed
            # in backward and (b, n, vocab) never hits HBM
            chunked = c.loss_chunk > 0 and not self.is_initializing()
            body = (nn.remat(_ce_segment, prevent_cse=False) if chunked
                    else _ce_segment)
            segments = loss_segments(c, c.loss_chunk if chunked else 0)
            # the leaves are cut once a step, not once a segment: the
            # segments' gradients add up at the cuts' shapes and meet the
            # whole leaf's once
            kernel, bias = self._head_leaves()
            heads = {cols: (kernel[:, cols[0]:cols[1]], bias[cols[0]:cols[1]])
                     for cols in dict.fromkeys(cols for _, cols in segments)}
            ce = jnp.concatenate(
                [body(self, out[:, r0:r1], labels[:, r0:r1], *heads[cols])
                 for (r0, r1), cols in segments], axis=1)
            loss_text = ce[:, :c.text_seq_len].mean()
            loss_img = ce[:, c.text_seq_len:].mean()
            loss = ((loss_text + c.loss_img_weight * loss_img)
                    / (c.loss_img_weight + 1))
        aux = {"loss_text": loss_text, "loss_img": loss_img}
        if c.mtp_depth:
            loss_mtp, mtp_counters = self._mtp_loss(out, tokens, labels,
                                                    heads, deterministic)
            loss = loss + c.mtp_loss_weight * loss_mtp
            aux["loss_mtp"] = loss_mtp
            counters = reduce_counters([counters, mtp_counters])
        return loss, {**aux, **counters}

    def _mtp_loss(self, hidden, tokens, labels, heads, deterministic: bool):
        """The multi-token-prediction pass: position ``i`` of the block's
        output predicts ``labels[i + 1]`` through the main head's leaves
        (``heads``: the cut kernels and biases by column range). The same
        text 1 : image 7 weighted cross-entropy over its n - 1 positions,
        each against the vocabulary of the position it predicts, chunked
        and rematerialised like the main head. Returns (loss, the block's
        counters)."""
        c = self.cfg
        remat = c.use_remat and not self.is_initializing()
        with jax.named_scope("mtp/merge"):
            merge = (nn.remat(_mtp_merge, prevent_cse=False) if remat
                     else _mtp_merge)
            x = merge(self, hidden, tokens)
        with jax.named_scope("mtp/block"):
            x, counters = self.mtp_block(x, deterministic=deterministic,
                                         return_aux=True)
        with jax.named_scope("loss/mtp"):
            chunked = c.loss_chunk > 0 and not self.is_initializing()
            body = (nn.remat(_ce_segment_mtp, prevent_cse=False) if chunked
                    else _ce_segment_mtp)
            ahead = labels[:, 1:]
            ce = jnp.concatenate(
                [body(self, x[:, r0:r1], ahead[:, r0:r1], *heads[cols])
                 for (r0, r1), cols in loss_segments(
                     c, c.loss_chunk if chunked else 0, shift=1)], axis=1)
            text = c.text_seq_len - 1
            loss = ((ce[:, :text].mean()
                     + c.loss_img_weight * ce[:, text:].mean())
                    / (c.loss_img_weight + 1))
        return loss, counters

    # -- generation --------------------------------------------------------
    def _prefill(self, text, image_prime: Optional[jnp.ndarray], batch: int,
                 dtype=jnp.float32, extra_slots: int = 0):
        c = self.cfg
        cache = self.transformer.init_cache(batch,
                                            c.total_seq_len + extra_slots,
                                            dtype)
        text_b = self.remap_and_bos(text)
        tokens = self.embed_text(text_b)
        if image_prime is not None and image_prime.shape[1] > 0:
            tokens = jnp.concatenate(
                [tokens, self.embed_image(image_prime)], axis=1)
        tokens = self._stabilize(tokens)
        y, cache = self.transformer.prefill(tokens, cache)
        logits = self._finish(y[:, -1:], (tokens.shape[1] - 1, 1))[:, 0]
        return logits, cache, tokens.shape[1]

    def _decode_one(self, token_id, img_pos, offset, cache, use_kernel=None):
        """Embed image token sampled at image position ``img_pos`` and advance."""
        tok = self._embed_image_ids(token_id[:, None])
        if not self.cfg.rotary_emb:
            emb = self.image_pos_emb()
            tok = tok + jax.lax.dynamic_slice_in_dim(emb, img_pos, 1, axis=0)[None]
        tok = self._stabilize(tok)
        y, cache = self.transformer.decode_step(tok, cache, offset,
                                                use_kernel=use_kernel)
        logits = self._finish(y, (offset, 1))[:, 0]
        return logits, cache

    def generate_images_tokens(self, text, key, *, filter_thres: float = 0.5,
                               temperature: float = 1.0, cond_scale: float = 1.0,
                               image_prime: Optional[jnp.ndarray] = None,
                               cache_dtype=jnp.float32,
                               topk_approx: bool = False,
                               use_kernel=None):
        """AR-sample the full image token sequence. Returns (b, image_seq_len)
        int32 codebook ids. ``text`` must be (b, text_seq_len).
        ``cache_dtype=bf16`` halves the KV-cache traffic of the decode loop;
        ``cache_dtype=jnp.int8`` halves it again via per-position symmetric
        quantization (ops/attention.KVCache — sampling itself always runs on
        f32 logits). ``topk_approx`` swaps the exact per-step top-k sort for
        TPU's approximate top-k unit (ops/sampling.top_k_filter) — the sort
        is ~17% of decode wall time at batch 64. ``use_kernel`` pins the
        Pallas decode-kernel selection (None = shape-gated auto on TPU,
        always dense elsewhere). Bitwise parity with a serve engine is a
        CPU-mesh property; on the TPU it does not hold for either setting
        (docs/SERVING.md "The exactness contract on the chip").
        (reference generate_images :490-557 minus vae decode/CLIP, which live in
        DalleWithVae)"""
        c = self.cfg
        b = text.shape[0]
        n_prime = 0 if image_prime is None else image_prime.shape[1]
        n_steps = c.image_seq_len - n_prime
        use_cfg = cond_scale != 1.0

        logits, cache, prefix_len = self._prefill(text, image_prime, b,
                                                  dtype=cache_dtype)
        if use_cfg:
            null_text = jnp.zeros_like(text)  # all-pad after remap
            null_logits, null_cache, _ = self._prefill(null_text, image_prime,
                                                       b, dtype=cache_dtype)
            logits = null_logits + (logits - null_logits) * cond_scale

        def sample_from(logits, k):
            band = logits[:, self.num_text_tokens:]  # image band only
            filtered = top_k_filter(band, thres=filter_thres,
                                    approx=topk_approx)
            return gumbel_sample(k, filtered, temperature=temperature).astype(jnp.int32)

        def body(carry, i):
            logits, cache, null_cache, k = carry
            k, sub = jax.random.split(k)
            tok = sample_from(logits, sub)
            img_pos = n_prime + i
            offset = prefix_len + i
            new_logits, cache = self._decode_one(tok, img_pos, offset, cache,
                                                 use_kernel)
            if use_cfg:
                nl, null_cache = self._decode_one(tok, img_pos, offset,
                                                  null_cache, use_kernel)
                new_logits = nl + (new_logits - nl) * cond_scale
            return (new_logits, cache, null_cache, k), tok

        # when CFG is off the null slot carries a scalar placeholder, not a
        # second copy of the cache
        init = (logits, cache, null_cache if use_cfg else jnp.zeros(()), key)
        (last_logits, *_), toks = nn.scan(
            lambda m, carry, i: body(carry, i),
            variable_broadcast=("params", "quant"),
            split_rngs={"params": False},
            length=n_steps - 1)(self, init, jnp.arange(n_steps - 1))
        # final token sampled from the last logits (no decode needed after it)
        final = sample_from(last_logits, jax.random.fold_in(key, n_steps))
        toks = jnp.moveaxis(toks, 0, 1)  # (b, n_steps-1)
        out = jnp.concatenate([toks, final[:, None]], axis=1)
        if image_prime is not None and n_prime > 0:
            out = jnp.concatenate([image_prime, out], axis=1)
        return out

    def generate_images_tokens_speculative(
            self, text, key, *, gamma: int = 4, draft: str = "row",
            filter_thres: float = 0.5, temperature: float = 1.0,
            cache_dtype=jnp.float32, topk_approx: bool = False,
            return_stats: bool = False):
        """Draft-free speculative AR sampling: each round drafts ``gamma``
        tokens with a zero-cost image prior, verifies them in ONE windowed
        forward (w = gamma+1 tokens ≈ the cost of a single decode step —
        batched decode is weight/KV-bandwidth-bound, so extra window tokens
        ride the same HBM streams), and commits the accepted prefix + one
        token. Rows accept independently (per-row cache offsets/lengths).

        Sampling semantics are EXACT for any draft quality: token t is
        always argmax(top_k(logits_t)/T + gumbel(key_t_row)) with
        logits_t computed from the committed prefix — rejected drafts only
        cost wasted work, never bias (gamma=0 degenerates to the sequential
        loop and must produce identical tokens; asserted by
        tests/test_speculative.py). Keys are per-(step, row) fold-ins —
        a different stream from generate_images_tokens' split chain, so
        outputs match that path distributionally, not bitwise.

        ``draft``: "row" = the committed token one grid-row above (the
        2D-autoregressive prior — vertically continuous images accept
        long runs); "repeat" = repeat the last sampled token (flat-region
        prior). Reference bar: the strictly sequential generate_images loop
        (dalle_pytorch/dalle_pytorch.py:523-546).

        ``return_stats``: also return (rounds_used, committed_total) —
        committed_total / (batch · rounds_used) is the per-row acceptance
        rate in committed tokens per round."""
        c = self.cfg
        b = text.shape[0]
        n_steps = c.image_seq_len
        fmap = c.image_fmap_size
        assert gamma >= 0
        assert draft in ("row", "repeat")
        if draft == "row":
            assert gamma < fmap, (
                f"'row' draft needs gamma < image_fmap_size ({fmap}); the "
                f"row-above token of a draft slot must already be committed")
        w = gamma + 1
        arange_b = jnp.arange(b)

        logits0, cache, prefix_len = self._prefill(
            text, None, b, dtype=cache_dtype, extra_slots=gamma)

        def sample_rows(logits, t_idx):
            """Token at per-row step ``t_idx`` from (b, V) logits — the
            committed key discipline key(step, row)."""
            keys = jax.vmap(lambda t, r: jax.random.fold_in(
                jax.random.fold_in(key, t), r))(t_idx, arange_b)
            return gumbel_sample_rows(keys, logits[:, self.num_text_tokens:],
                                      thres=filter_thres,
                                      temperature=temperature,
                                      approx=topk_approx)

        def draft_tokens(tok0, out_buf, t_idx):
            if gamma == 0:
                return jnp.zeros((b, 0), jnp.int32)
            p = t_idx[:, None] + jnp.arange(1, gamma + 1)[None, :]  # (b, γ)
            if draft == "row":
                src = jnp.clip(p - fmap, 0, n_steps - 1)
                above = jnp.take_along_axis(out_buf, src, axis=1)
                return jnp.where(p - fmap >= 0, above, tok0[:, None])
            return jnp.broadcast_to(tok0[:, None], (b, gamma))

        img_allow = self.logits_allow[c.text_seq_len]   # every image row ==

        def finish_rows(y):
            if c.stable:
                y = self.norm_by_max(y)
            logits = self._logits(y)
            return jnp.where(img_allow[None, None], logits, MASK_VALUE)

        def body(carry):
            out_buf, t_idx, logits, cache, rounds, committed_total = carry
            t_eff = jnp.minimum(t_idx, n_steps - 1)   # finished rows idle
            tok0 = sample_rows(logits, t_eff)
            drafts = draft_tokens(tok0, out_buf, t_eff)
            window = jnp.concatenate([tok0[:, None], drafts], axis=1)
            emb = self._embed_image_ids(window)
            if not c.rotary_emb:
                img_pos = t_eff[:, None] + jnp.arange(w)[None, :]
                emb = emb + jnp.take(self.image_pos_emb(),
                                     jnp.clip(img_pos, 0, n_steps - 1),
                                     axis=0)
            emb = self._stabilize(emb)
            y, cache = self.transformer.decode_window(
                emb, cache, prefix_len + t_eff)
            logits_w = finish_rows(y)                    # (b, w, V)
            cands = jnp.stack(
                [sample_rows(logits_w[:, j], t_eff + 1 + j)
                 for j in range(w)], axis=1)             # tokens t+1..t+w
            if gamma > 0:
                eq = (drafts == cands[:, :gamma]).astype(jnp.int32)
                acc = jnp.cumprod(eq, axis=1).sum(axis=1)   # (b,) 0..γ
            else:
                acc = jnp.zeros((b,), jnp.int32)
            # commit window[:, j] at index t+j for j ≤ acc (window[j] ==
            # cands[j-1] wherever accepted); drop out-of-range / finished
            idx = t_eff[:, None] + jnp.arange(w)[None, :]
            keep = ((jnp.arange(w)[None, :] <= acc[:, None])
                    & (idx < n_steps) & (t_idx[:, None] < n_steps))
            safe_idx = jnp.where(keep, idx, n_steps)
            out_buf = out_buf.at[arange_b[:, None], safe_idx].set(
                window, mode="drop")
            # carry logits after the LAST committed token: exact, because
            # cache slots ≤ t+acc hold exactly the committed tokens
            new_logits = jnp.take_along_axis(
                logits_w, acc[:, None, None], axis=1)[:, 0]
            # clamp at the sequence end: an accepted run crossing n_steps
            # only commits the in-range part (its writes were dropped above)
            step = jnp.where(t_idx < n_steps,
                             jnp.minimum(acc + 1, n_steps - t_idx), 0)
            return (out_buf, t_idx + step, new_logits, cache, rounds + 1,
                    committed_total + step.sum())

        def cond(carry):
            return jnp.any(carry[1] < n_steps)

        init = (jnp.zeros((b, n_steps), jnp.int32), jnp.zeros((b,), jnp.int32),
                logits0, cache, jnp.zeros((), jnp.int32),
                jnp.zeros((), jnp.int32))
        out_buf, _, _, _, rounds, committed = jax.lax.while_loop(
            cond, body, init)
        if return_stats:
            return out_buf, rounds, committed
        return out_buf

    # -- serving: per-row-length decode primitives (dalle_tpu/serve) -------
    # The continuous-batching engine keeps B decode slots in ONE shared
    # cache; slots are at ragged positions (each carries its own prompt and
    # per-row length), so every device call below threads (b,) offset
    # vectors through transformer.decode_window. Rows that must not be
    # touched get offset == max_seq: their k/v scatter indices land entirely
    # out of bounds and are DROPPED (XLA scatter OOB semantics — the same
    # contract the speculative path's mode="drop" commit relies on), so a
    # parked row's cache is bit-identical before and after the call.
    #
    # Exactness contract (tests/test_serve.py): with cache max_seq ==
    # total_seq_len — the same size single-request generation uses — every
    # reduction in these paths has the same width as its sequential
    # counterpart, and each request's logits (hence tokens, under the same
    # key discipline) match generate_images_tokens bitwise, for any
    # admission order.

    def serve_img_logits(self, y):
        """(b, dim) hidden states → (b, V) masked logits. Every served
        position predicts image tokens, and the static allow-mask rows for
        positions ≥ text_seq_len are identical — one row serves them all
        (the same argument generate_images_tokens_speculative makes)."""
        return self._finish(y[:, None], (self.cfg.text_seq_len, 1))[:, 0]

    def serve_init_cache(self, batch: int, dtype=jnp.float32):
        """Shared decode cache for ``batch`` serve slots. max_seq is exactly
        total_seq_len so softmax reduce widths match single-request
        generation (bitwise exactness); the park offset is max_seq itself."""
        return self.transformer.init_cache(batch, self.cfg.total_seq_len,
                                           dtype)

    def serve_init_cache_paged(self, num_blocks: int, block_tokens: int,
                               dtype=jnp.float32):
        """Paged serve cache (graftpage): per-layer block pools; reads
        gather back to a dense total_seq_len view so reduce widths — and
        therefore every request's tokens — stay bitwise identical to the
        dense slab and to single-request generation. The engine injects its
        single page-table leaf into each layer per dispatch."""
        return self.transformer.init_cache_paged(
            num_blocks, block_tokens, self.cfg.total_seq_len, dtype)

    def serve_refill(self, text, cache, refill_mask, use_kernel=None):
        """Admission: prefill new prompts into SELECTED rows of the live
        multi-slot cache in one multi-row window. ``text`` (b, text_seq_len)
        int32 (rows with ``refill_mask`` False are ignored); refilled rows
        write their prompt k/v at [0, prefix_len) — overwriting the previous
        occupant — while every other row parks at offset max_seq. Returns
        (logits (b, V) for each refilled row's first image token, cache)."""
        S = cache["kv_0"].max_seq       # max_seq == the park offset
        text_b = self.remap_and_bos(text)
        tokens = self._stabilize(self.embed_text(text_b))
        offsets = jnp.where(refill_mask, 0, S)
        y, cache = self.transformer.decode_window(tokens, cache, offsets,
                                                  use_kernel=use_kernel)
        return self.serve_img_logits(y[:, -1]), cache

    def serve_refill_shared(self, text1, cache, refill_mask,
                            cache_dtype=jnp.float32):
        """Shared-prefix admission (graftloom): ONE b=1 text prefill —
        bitwise the sequential ``_prefill``, exactly ``serve_prefill_row`` —
        broadcast into every ``refill_mask`` row of the live multi-slot
        cache. N candidates of one prompt (a ``/v1/images`` fan-out) pay ONE
        prompt prefill instead of N: the prefix KV depends only on the text,
        never the seed, so copying the same bits into each sibling row is
        exact by construction — each candidate then decodes under its own
        RNG lane and stays bitwise identical to an independent
        single-candidate request (the PR4 bar, (N−1) prefills cheaper).
        Returns (logits (1, V) for the shared first image token, cache)."""
        logits1, cache1 = self.serve_prefill_row(text1,
                                                 cache_dtype=cache_dtype)
        cache = dict(cache)
        m2 = refill_mask[:, None, None]
        for name, small in cache1.items():
            big = cache[name]
            # (1, S, 2hd) broadcasts over the slot axis; unmasked rows keep
            # their occupant's cache bit-identically
            kv = jnp.where(m2, small.kv, big.kv)
            if big.scale is not None:
                sc = jnp.where(m2, small.scale, big.scale)
                cache[name] = big.replace(kv=kv, scale=sc)
            else:
                cache[name] = big.replace(kv=kv)
        return logits1, cache

    def serve_refill_window(self, ids, cache, refill_mask, start,
                            use_kernel=None):
        """Chunked-prefill admission: one bounded window of an already
        remapped+bos'd prompt (``ids`` (b, w), full-vocab token ids — the
        engine host-applies ``remap_and_bos`` and slices) written at
        absolute positions [start, start+w) of each ``refill_mask`` row.
        Dispatching the prompt as ceil(prefix/w) of these windows
        interleaved with decode iterations bounds how long one fat
        admission can stall its neighbors' tokens (p95 TTFT isolation);
        causality makes the chunked prefix bitwise identical to the one-shot
        ``serve_refill`` window — each chunk token attends exactly the cache
        prefix the full window would have shown it, at the same reduce
        widths. Returns (logits (b, V) from the window's LAST position —
        meaningful only on the final chunk — and the cache)."""
        S = cache["kv_0"].max_seq       # max_seq == the park offset
        n = ids.shape[1]
        tok = self._embed_text_ids(ids)
        if not self.cfg.rotary_emb:
            tok = tok + self.text_pos_emb(start + jnp.arange(n))
        tokens = self._stabilize(tok)
        offsets = jnp.where(refill_mask, start, S)
        y, cache = self.transformer.decode_window(tokens, cache, offsets,
                                                  use_kernel=use_kernel)
        return self.serve_img_logits(y[:, -1]), cache

    def serve_prefill_row(self, text, cache_dtype=jnp.float32):
        """Single-request prefill for the engine's per-row admission path:
        (1, text_seq_len) text → (logits (1, V), fresh b=1 cache sized
        total_seq_len). Bitwise identical to the sequential ``_prefill`` by
        construction — the engine scatters the cache row into the shared
        multi-slot cache (cheaper than the multi-row refill window when
        admitting a small fraction of the slots)."""
        logits, cache, _ = self._prefill(text, None, 1, dtype=cache_dtype,
                                         extra_slots=0)
        return logits, cache

    def serve_decode(self, tok, img_pos, offsets, cache, use_kernel=None):
        """One decode step for every slot at PER-ROW positions: ``tok`` (b,)
        image-band token ids, ``img_pos`` (b,) image grid positions (axial
        table rows when rotary is off), ``offsets`` (b,) absolute cache
        write positions — parked rows pass max_seq (write dropped, output
        discarded by the engine). Returns (logits (b, V), cache)."""
        c = self.cfg
        emb = self._embed_image_ids(tok[:, None])
        if not c.rotary_emb:
            pos = jnp.clip(img_pos, 0, c.image_seq_len - 1)
            emb = emb + jnp.take(self.image_pos_emb(), pos, axis=0)[:, None]
        emb = self._stabilize(emb)
        y, cache = self.transformer.decode_window(emb, cache, offsets,
                                                  use_kernel=use_kernel)
        return self.serve_img_logits(y[:, 0]), cache

    def generate_texts_tokens(self, key, text: Optional[jnp.ndarray] = None, *,
                              batch: int = 1, filter_thres: float = 0.5,
                              temperature: float = 1.0):
        """Complete a text prefix to text_seq_len tokens by AR sampling over the
        text band (reference generate_texts :443-488). Returns (b, text_seq_len)."""
        c = self.cfg
        if text is None:
            text = jnp.zeros((batch, 0), jnp.int32)
        b, start = text.shape
        assert start < c.text_seq_len, (
            f"text prefix must be shorter than text_seq_len={c.text_seq_len}, "
            f"got {start}")
        cache = self.transformer.init_cache(b, c.total_seq_len)
        # prefix: bos + given tokens (no pad remap — these are real tokens)
        ids = jnp.pad(text, ((0, 0), (1, 0)))
        tokens = self._stabilize(self.embed_text(ids))
        y, cache = self.transformer.prefill(tokens, cache)
        logits = self._finish(y[:, -1:], (start, 1))[:, 0]

        def sample_text(logits, k):
            filtered = top_k_filter(logits[:, :self.num_text_tokens],
                                    thres=filter_thres)
            return gumbel_sample(k, filtered, temperature=temperature).astype(jnp.int32)

        def body(carry, i):
            logits, cache, k = carry
            k, sub = jax.random.split(k)
            tok = sample_text(logits, sub)
            pos = start + 1 + i  # position of this token (after bos)
            emb = self._embed_text_ids(tok[:, None])
            if not c.rotary_emb:
                emb = emb + self.text_pos_emb(jnp.array([pos]))[None]
            emb = self._stabilize(emb)
            y, cache = self.transformer.decode_step(emb, cache, pos)
            new_logits = self._finish(y, (pos, 1))[:, 0]
            return (new_logits, cache, k), tok

        n_new = c.text_seq_len - start
        (last_logits, *_), toks = nn.scan(
            lambda m, carry, i: body(carry, i),
            variable_broadcast=("params", "quant"),
            split_rngs={"params": False},
            length=n_new - 1)(self, (logits, cache, key), jnp.arange(n_new - 1))
        final = sample_text(last_logits, jax.random.fold_in(key, n_new))
        toks = jnp.moveaxis(toks, 0, 1)
        return jnp.concatenate([text, toks, final[:, None]], axis=1)


def table_grad_paths(cfg: DalleConfig, dtype, batch: int) -> Dict[str, dict]:
    """What the training forward's token lookups give as their tables'
    backward, per table: ``ops/table_lookup.grad_path``'s name beside the
    shapes it was chosen from and the ids a step of ``batch`` looks up.
    ``dtype`` is the compute dtype the tables are cast to."""
    text_ids = batch * (cfg.text_seq_len + 1)
    image_ids = batch * cfg.image_seq_len
    if cfg.share_input_output_emb:
        tables = {"shared_emb": (cfg.total_tokens, text_ids + image_ids)}
    else:
        tables = {"text_emb": (cfg.total_tokens - cfg.image_vocab_size,
                               text_ids),
                  "image_emb": (cfg.image_vocab_size, image_ids)}
    return {name: {"path": grad_path(rows, cfg.dim, dtype), "rows": rows,
                   "width": cfg.dim, "ids": ids}
            for name, (rows, ids) in tables.items()}


def loss_head(cfg: DalleConfig, batch: int) -> dict:
    """What the training loss's head computes, from shapes alone: its
    segments (``rows`` of positions against ``cols`` of the vocabulary, the
    logits of a step being ``batch`` of each), and per sequence the logits
    it builds beside the full-width head's."""
    def listed(segments):
        return [{"rows": list(rows), "cols": list(cols)}
                for rows, cols in segments]

    def elements(segments):
        return sum((r1 - r0) * (c1 - c0) for (r0, r1), (c0, c1) in segments)
    segments = loss_segments(cfg, cfg.loss_chunk)
    out = {"segments": listed(segments), "batch": batch,
           "elements_computed": elements(segments),
           "elements_full": cfg.total_seq_len * cfg.total_tokens}
    if cfg.mtp_depth:
        # the multi-token-prediction pass's own segments, one row fewer
        ahead = loss_segments(cfg, cfg.loss_chunk, shift=1)
        out["mtp"] = {"segments": listed(ahead),
                      "elements_computed": elements(ahead)}
    return out


def init_dalle(cfg: DalleConfig, key: jax.Array, batch: int = 1, sp_mesh=None):
    model = DALLE(cfg, sp_mesh=sp_mesh)
    text = jnp.zeros((batch, cfg.text_seq_len), jnp.int32)
    img = jnp.zeros((batch, cfg.image_seq_len), jnp.int32)
    params = model.init({"params": key, "cfg": key}, text, img, return_loss=True)
    return model, params
