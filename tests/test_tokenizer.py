"""Byte-level BPE tokenizer."""

from hypothesis import given, settings, strategies as st

from repro.tokenizer import BPETokenizer, train_bpe


def test_roundtrip_basic(small_tokenizer):
    tok = small_tokenizer
    for s in ['{"a": [1, 2.5], "b": true}', "int f() { return 1; }",
              "hello world", "", "ünïcødé"]:
        assert tok.decode(tok.encode(s)) == s
        assert tok.decode(tok.encode_greedy(s)) == s


@settings(max_examples=50, deadline=None)
@given(st.binary(max_size=40))
def test_roundtrip_arbitrary_bytes(data):
    tok = train_bpe(b"ababab abab cd cd", vocab_size=260)
    assert tok.decode_bytes(tok.encode_bytes(data)) == data


def test_byte_coverage(small_tokenizer):
    tok = small_tokenizer
    for b in range(256):
        assert tok.vocab[b] == bytes([b])


def test_specials(small_tokenizer):
    tok = small_tokenizer
    assert tok.vocab[tok.eos_id] is None
    assert tok.vocab[tok.pad_id] is None
    assert tok.eos_id == tok.vocab_size - 1


def test_merges_learned():
    corpus = b'{"key": 1}\n' * 50
    tok = train_bpe(corpus, vocab_size=300)
    assert tok.vocab_size > 259
    ids = tok.encode('{"key": 1}')
    assert len(ids) < len('{"key": 1}')  # merges actually applied


def test_save_load(tmp_path, small_tokenizer):
    p = tmp_path / "tok.json"
    small_tokenizer.save(p)
    tok2 = BPETokenizer.load(p)
    assert tok2.vocab == small_tokenizer.vocab
    s = '{"x": [true, null]}'
    assert tok2.encode(s) == small_tokenizer.encode(s)
