"""``encode_titles`` and ``ngram_features`` against the per-title oracle
(``tests/featurize_oracle.py``), bit for bit: the codes, the lengths and
their dtypes, and the float32 features compared as ``uint32``.

Inputs: DS1- and DS2-shaped corpora from the benchmark's generator at a
few thousand records; titles cut at ``max_len``, one inside a multi-byte
UTF-8 character; empty, one- and two-byte titles, embedded NUL bytes and
a lone surrogate; batches of no title and of one. Each runs at every
``dim`` (100 is no power of two), window size ``n`` and ``max_len``.
"""
import functools
import importlib.util
import json
import sys
from pathlib import Path

import numpy as np
import pytest

import featurize_oracle as oracle
from repro.er.encode import encode_titles, ngram_features

BENCH = Path(__file__).resolve().parents[1] / "bench"


@functools.lru_cache(maxsize=None)
def _corpus(config: str, n_records: int, seed: int):
    spec = importlib.util.spec_from_file_location(
        "bench_corpus", BENCH / "corpus.py")
    corpus = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = corpus   # its dataclass looks itself up there
    spec.loader.exec_module(corpus)
    params = json.loads((BENCH / "configs" / f"{config}.json").read_text())
    params["n_records"] = n_records
    return tuple(corpus.build_corpus(params, seed).titles)


EDGE = (
    "", "a", "ab", "abc", "x" * 31, "y" * 32, "z" * 33, "w" * 64, "v" * 90,
    # a two-byte character across byte 32 and one across byte 64
    "p" * 31 + "é" + "tail", "q" * 63 + "ü" + "tail",
    "€" * 30,                         # three-byte characters cut anywhere
    "a\x00b", "\x00", "\x00\x00\x00\x00", "nul at end\x00",
    "lone \ud800 surrogate", "\udfff",
    "  spaced  out  ", "mixed Ünïcödé title 0042",
)

TITLES = {
    "ds1": lambda: _corpus("ds1_products", 3_000, 2 ** 31 + 11),
    "ds2": lambda: _corpus("ds2_publications", 4_000, 7),
    "edge": lambda: EDGE,
    "long": lambda: tuple(t * 5 for t in _corpus("ds1_products", 500, 3)),
    "empty_batch": lambda: (),
    "one_title": lambda: ("abc laptop phone 1234",),
    "one_short": lambda: ("ab",),
    # full-width rows back to back, a row's last bucket equal to the
    # next row's first
    "repeats": lambda: ("aaaaaaaaa",) * 20 + ("aaaa bbbb", "bbbb aaaa") * 20,
}


@pytest.mark.parametrize("max_len", [32, 64])
@pytest.mark.parametrize("n", [2, 3, 4])
@pytest.mark.parametrize("dim", [64, 100, 256])
@pytest.mark.parametrize("batch", sorted(TITLES))
def test_featurize_matches_oracle(batch, dim, n, max_len):
    titles = list(TITLES[batch]())
    codes, lens = encode_titles(titles, max_len=max_len)
    want_codes, want_lens = oracle.encode_titles(titles, max_len=max_len)
    assert codes.dtype == np.uint8 and lens.dtype == np.int32
    assert codes.shape == (len(titles), max_len) == want_codes.shape
    np.testing.assert_array_equal(codes, want_codes)
    np.testing.assert_array_equal(lens, want_lens)

    want = oracle.ngram_features(want_codes, dim=dim, n=n,
                                 lengths=want_lens)
    for got in (ngram_features(codes, dim=dim, n=n, lengths=lens),
                ngram_features(titles, dim=dim, n=n, max_len=max_len)):
        assert got.dtype == np.float32 and got.shape == (len(titles), dim)
        np.testing.assert_array_equal(got.view(np.uint32),
                                      want.view(np.uint32))
