"""ByteTokenizer tests, including streaming UTF-8 boundary handling."""

from polykey_tpu.engine.tokenizer import ByteTokenizer, load_tokenizer


def test_roundtrip():
    tok = ByteTokenizer()
    ids = tok.encode("hello, world")
    assert ids[0] == tok.bos_id
    assert tok.decode(ids) == "hello, world"


def test_unicode_roundtrip():
    tok = ByteTokenizer()
    text = "héllo → 世界 🌍"
    assert tok.decode(tok.encode(text)) == text


def test_specials_skipped_in_decode():
    tok = ByteTokenizer()
    ids = [tok.bos_id] + tok.encode("hi")[1:] + [tok.eos_id, tok.pad_id]
    assert tok.decode(ids) == "hi"


def test_incremental_decode_splits_multibyte():
    tok = ByteTokenizer()
    ids = tok.encode("a→b")[1:]  # strip bos; '→' is 3 bytes
    # Feed one token at a time; concatenation must equal the full string and
    # no chunk may contain a replacement character.
    state = b""
    out = []
    for i in ids:
        chunk, state = tok.decode_incremental([i], state)
        assert "�" not in chunk
        out.append(chunk)
    assert "".join(out) == "a→b"
    assert state == b""


def test_incremental_decode_replaces_bytes_that_never_complete():
    """A random-init model emits any byte, 0xFD included — a lead byte no
    valid UTF-8 sequence starts with. It must stream as U+FFFD (what
    decode() yields) instead of being held forever with every later byte
    behind it: the held tail stays within one incomplete sequence."""
    tok = ByteTokenizer()
    ids = [3 + b for b in b"\xfdab\xe2\x86\x92c\xff\xe2\x86"]
    state, out = b"", []
    for i in ids:
        chunk, state = tok.decode_incremental([i], state)
        assert len(state) <= 3
        out.append(chunk)
    assert "".join(out) == "\ufffdab→c\ufffd"
    assert state == b"\xe2\x86"       # an incomplete tail still waits


def test_load_tokenizer_byte():
    tok = load_tokenizer("byte")
    assert isinstance(tok, ByteTokenizer)


def test_incremental_detokenizer_context_dependent():
    """The bounded-window detokenizer must reproduce full-prefix decoding
    for a context-DEPENDENT tokenizer: this fake mixes whole-word pieces
    with UTF-8 byte-fallback ids (sentencepiece-style), so a multi-byte
    character's text only exists once all its bytes arrived, and partial
    sequences must be held back (never streamed as U+FFFD)."""
    from polykey_tpu.engine.tokenizer import IncrementalDetokenizer

    euro = "€".encode("utf-8")  # 3 bytes -> ids 100, 101, 102

    class ByteFallbackTok:
        pieces = {0: b"he", 1: b"llo", 2: b" wor", 3: b"ld", 4: b" ",
                  100: euro[0:1], 101: euro[1:2], 102: euro[2:3]}

        def decode(self, ids):
            return b"".join(self.pieces[i] for i in ids).decode(
                "utf-8", errors="replace"
            )

    tok = ByteFallbackTok()
    ids = [0, 1, 4, 100, 101, 102, 2, 3]
    detok = IncrementalDetokenizer(tok)
    chunks = [detok.push(i) for i in ids]
    assert "�" not in "".join(chunks)
    # Bytes of '€' are held until the character completes.
    assert chunks[3] == "" and chunks[4] == "" and chunks[5] == "€"
    assert "".join(chunks) + detok.flush() == tok.decode(ids) == "hello € world"
    # Trailing incomplete sequence: held back by push, surfaced by flush.
    detok2 = IncrementalDetokenizer(tok)
    out = "".join(detok2.push(i) for i in [0, 100, 101])
    assert out == "he"
    # Python collapses the incomplete trailing sequence to one U+FFFD.
    assert detok2.flush() == "�"
