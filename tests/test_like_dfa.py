"""Vectorized LIKE (bit-parallel NFA over the dictionary) vs the exact
re-based oracle (reference: likematcher/DenseDfaMatcher.java:23)."""

import random
import re
import string

import numpy as np
import pytest

from trino_tpu.ops.like_dfa import VECTOR_THRESHOLD, like_match
from trino_tpu.ops.expr import like_to_regex


def _dict(values):
    out = np.empty(len(values), dtype=object)
    for i, v in enumerate(values):
        out[i] = v
    return out


def _oracle(dictionary, pattern, escape=None):
    rx = re.compile(like_to_regex(pattern, escape), re.DOTALL)
    return np.array([rx.fullmatch(str(v)) is not None for v in dictionary])


PATTERNS = [
    "abc", "%", "%%", "a%", "%a", "%bc%", "a_c", "_", "__", "a%b%c",
    "%a_b%", "", "%%a%%", "a%%_b", "ab_", "%xyz", "x%y%z%", "a",
]


@pytest.mark.parametrize("pattern", PATTERNS)
def test_vector_matches_re(pattern):
    rng = random.Random(42)
    alphabet = "abcxyz_%"
    vals = ["".join(rng.choice(alphabet) for _ in range(rng.randrange(0, 12)))
            for _ in range(VECTOR_THRESHOLD + 500)]
    vals.extend(["", "a", "abc", "aXc", "abcabc", "ab", "a" * 70])
    d = _dict(sorted(set(vals)))
    got, _, path = like_match(d, pattern)
    assert path == "vector"
    want = _oracle(d, pattern)
    diff = np.nonzero(got != want)[0]
    assert not len(diff), (pattern, [d[i] for i in diff[:5]])


def test_escape_and_unicode_fallback():
    d = _dict(["100%", "100x", "naïve", "a_c", "abc"])
    got = like_match(d, "100\\%", "\\")[0]
    assert got.tolist() == [True, False, False, False, False]
    # unicode literal falls back to re (codepoint >= 255 guard)
    big = _dict(sorted({f"naïve{i}" if i % 3 else f"x{i}"
                        for i in range(VECTOR_THRESHOLD + 10)}))
    got = like_match(big, "naïve%")[0]
    want = _oracle(big, "naïve%")
    assert (got == want).all()


def test_engine_like_still_correct():
    from trino_tpu.connectors.catalog import default_catalog
    from trino_tpu.runner import StandaloneQueryRunner

    r = StandaloneQueryRunner(default_catalog(scale_factor=0.01))
    rows = r.execute("select count(*) from customer "
                     "where c_mktsegment like 'BUILD%'").rows()
    rows2 = r.execute("select count(*) from customer "
                      "where c_mktsegment = 'BUILDING'").rows()
    assert rows == rows2 and rows[0][0] > 0


def test_embedded_nul_falls_back_to_exact():
    """Strings containing '\\x00' can't be measured from the codepoint
    matrix (padding is also 0) — the vector path must defer to re
    (advisor r4 low)."""
    vals = {f"k{i}" for i in range(VECTOR_THRESHOLD + 10)}
    vals |= {"a\x00b", "a\x00", "\x00", "ab", "a", "a\x00bXtail", "k1\x00"}
    d = _dict(sorted(vals))
    for pattern in ["a_b", "a%", "_", "ab", "a\x00b%"]:
        got, _, path = like_match(d, pattern)
        assert path == "re"
        want = _oracle(d, pattern)
        diff = np.nonzero(got != want)[0]
        assert not len(diff), (pattern, [repr(d[i]) for i in diff[:5]])
