"""Text tokenizer tests — the L1 layer (SURVEY.md §2.3).

The golden ids below were produced by the reference SimpleTokenizer
(dalle_pytorch/tokenizer.py:55-152) over the shipped CLIP merges vocabulary;
the default tokenizer must reproduce them exactly (vocab 49,408).
"""

import numpy as np
import pytest

from dalle_tpu.text.bpe import BPE, DEFAULT_VOCAB_PATH, load_merges, train_bpe
from dalle_tpu.text.tokenizer import SimpleTokenizer, YttmTokenizer, get_tokenizer

# (text, reference token ids) — reference tokenizer.py encode() outputs
GOLDEN = [
    ("a cloudy sky at sunset", [320, 13106, 2390, 536, 3424]),
    ("Hello, World! 123", [3306, 267, 1002, 256, 272, 273, 274]),
    ("the quick brown fox jumps over the lazy dog.",
     [518, 3712, 2866, 3240, 18911, 962, 518, 10753, 1929, 269]),
    ("an oil painting of a fox's tail - impressionism",
     [550, 2870, 3086, 539, 320, 3240, 568, 4132, 268, 36114]),
    ("unicode text with emoji \U0001F308 mixed in",
     [7648, 19639, 4160, 593, 16327, 13042, 6780, 530]),
    ("supercalifragilisticexpialidocious antidisestablishmentarianism",
     [1642, 2857, 13093, 2076, 5868, 26850, 835, 639, 38466, 3120, 4262,
      7726, 12658, 1585, 44351]),
    ("A RAINBOW-colored umbrella;   with    weird whitespace",
     [320, 6286, 268, 11775, 17143, 282, 593, 5613, 4699, 2138]),
]


@pytest.fixture(scope="module")
def tok():
    return SimpleTokenizer()


class TestDefaultVocab:
    def test_vocab_file_ships(self):
        assert DEFAULT_VOCAB_PATH.exists()

    def test_default_vocab_size_is_clip(self, tok):
        # 256 bytes + 256 byte+'</w>' + 48,894 merges + 2 specials
        assert tok.vocab_size == 49408

    @pytest.mark.parametrize("text,ids", GOLDEN, ids=[t[:20] for t, _ in GOLDEN])
    def test_reference_golden_ids(self, tok, text, ids):
        assert tok.encode(text) == ids

    @pytest.mark.parametrize("text,ids", GOLDEN, ids=[t[:20] for t, _ in GOLDEN])
    def test_round_trip(self, tok, text, ids):
        # decode emits one space per word-token (same as the reference: every
        # '</w>' becomes ' '), so punctuation comes back space-separated and
        # text lowercased/whitespace-collapsed
        from dalle_tpu.text.bpe import WORD_PAT, clean_text
        expect = " ".join(WORD_PAT.findall(clean_text(text)))
        assert tok.decode(tok.encode(text)) == expect

    def test_tokenize_contract(self, tok):
        out = tok.tokenize(["a cloudy sky at sunset", "hello"],
                           context_length=16)
        assert out.shape == (2, 16) and out.dtype == np.int32
        assert out[0, :5].tolist() == GOLDEN[0][1]
        assert (out[0, 5:] == 0).all() and (out[1, 1:] == 0).all()

    def test_tokenize_truncation(self, tok):
        long = "painting " * 64
        with pytest.raises(RuntimeError):
            tok.tokenize(long, context_length=8)
        out = tok.tokenize(long, context_length=8, truncate_text=True)
        assert out.shape == (1, 8) and (out != 0).all()


class TestByteLevelFallback:
    def test_explicit_empty_merges_gives_byte_level(self):
        t = SimpleTokenizer(bpe_path=None, merges=[])
        assert t.vocab_size == 514
        assert t.decode(t.encode("hello world")) == "hello world"


class TestMergesIO:
    def test_gz_and_plain_load_identically(self, tmp_path):
        import gzip
        merges = load_merges(DEFAULT_VOCAB_PATH, limit=100)
        plain = tmp_path / "m.txt"
        plain.write_text("#version: test\n" +
                         "\n".join(f"{a} {b}" for a, b in merges))
        assert load_merges(plain) == merges

    def test_clip_header_is_skipped(self):
        merges = load_merges(DEFAULT_VOCAB_PATH, limit=3)
        # first real merge of the CLIP vocab is 'i n' (file line 2)
        assert merges[0] == ("i", "n")


class TestTrainFlow:
    def test_train_save_load(self, tmp_path):
        corpus = ["red square blue circle"] * 50 + ["green triangle"] * 30
        path = tmp_path / "learned.txt"
        t = SimpleTokenizer.train(corpus, num_merges=32, save_path=str(path))
        assert t.vocab_size == 514 + len(t.bpe.merges)
        reloaded = YttmTokenizer(str(path))
        assert reloaded.encode("red square") == t.encode("red square")

    def test_get_tokenizer_registry_default(self):
        t = get_tokenizer("simple")
        assert t.vocab_size == 49408


class TestChineseTokenizer:
    def test_local_vocab_file(self, tmp_path):
        """ChineseTokenizer from a local WordPiece vocab (the offline path —
        no hub access in this environment)."""
        pytest.importorskip("transformers")
        vocab = tmp_path / "vocab.txt"
        vocab.write_text("\n".join(
            ["[PAD]", "[UNK]", "[CLS]", "[SEP]", "[MASK]",
             "你", "好", "世", "界", "猫", "红", "色"]) + "\n")
        from dalle_tpu.text.tokenizer import ChineseTokenizer
        tok = ChineseTokenizer(str(vocab))
        assert tok.vocab_size == 12
        ids = tok.encode("你好世界")
        assert ids == [5, 6, 7, 8]
        out = tok.tokenize(["红色猫"], context_length=8)
        assert out.shape == (1, 8) and out.dtype == np.int32
        assert out[0, :3].tolist() == [10, 11, 9]
        assert "你 好" in tok.decode(ids) or "你好" in tok.decode(ids)

    def test_default_falls_back_to_vendored_vocab(self, monkeypatch):
        """get_tokenizer('chinese') must be executable offline: the default
        hub model falls back to the vendored mini WordPiece vocab
        (text/data/chinese_vocab_mini.txt) with a warning (VERDICT r2 #8).
        from_pretrained is stubbed to raise OSError — env-var tricks
        (HF_HUB_OFFLINE) bind at transformers import time and would not
        force the branch on a machine with the model cached."""
        transformers = pytest.importorskip("transformers")
        from dalle_tpu.text.tokenizer import ChineseTokenizer, get_tokenizer
        assert ChineseTokenizer.VENDORED_VOCAB.is_file()

        def unreachable(*a, **k):
            raise OSError("hub unreachable (test stub)")

        monkeypatch.setattr(transformers.BertTokenizer, "from_pretrained",
                            unreachable)
        with pytest.warns(UserWarning, match="vendored mini vocab"):
            tok = get_tokenizer("chinese")
        assert tok.vocab_size >= 150
        ids = tok.encode("红色圆形")
        assert len(ids) == 4 and all(i > 4 for i in ids)   # no [UNK] (id 1)
        round_trip = tok.decode(ids).replace(" ", "")
        assert round_trip == "红色圆形"


def test_native_core_binary_is_named_by_its_source_digest():
    """A binary copied along with the tree (file times do not survive a
    copy) loads only if it was built from exactly the committed source."""
    import hashlib
    from dalle_tpu.text import native
    digest = hashlib.sha256(native._SRC.read_bytes()).hexdigest()[:16]
    assert native._lib_path().name == f"libbpe_core.{digest}.so"
    if native.NativeBPE.available():
        built = sorted(p.name for p in native._HERE.glob("libbpe_core*.so"))
        assert built == [native._lib_path().name]
