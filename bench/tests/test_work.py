"""Byte and operation counts of each roofline (bench/work.py) against hand
counts, and their independence of the program's plan layout."""
import numpy as np
import pytest

from bench import work
from bench.generators import matgen, poisson2d
from bench.references import ilu1


def _counts(m):
    n = m["n"]
    p_indptr, p_indices, diag = ilu1.pattern(n, m["indptr"], m["indices"])
    return n, len(m["data"]), len(p_indices), int(diag.sum()), \
        work.update_count(n, p_indptr, p_indices, diag, chunk_rows=3)


def test_poisson_2x2_by_hand():
    # rows {0,1,2} {0,1,3} {0,2,3} {1,2,3}; ILU(1) fills (1,2) and (2,1);
    # lower entries 5; updates: row 1 by 0 -> 2, row 2 by 0 -> 2 and by 1
    # -> 2, row 3 by 1 -> 2 and by 2 -> 1
    m = poisson2d.generate({"nx": 2}, np.random.default_rng(0))
    n, nnz_a, nnz_f, nnz_l, ups = _counts(m)
    assert (n, nnz_a, nnz_f, nnz_l, ups) == (4, 12, 14, 5, 9)
    assert work.spmv(n, nnz_a) == {"bytes": 8 * 12 + 4 * 5 + 8 * 4, "ops": 24}
    assert work.sweep(n, nnz_f) == {"bytes": 8 * 14 + 8 * 5 + 8 * 4, "ops": 2 * 10 + 4}
    assert work.factor(n, nnz_a, nnz_f, nnz_l, ups) == {
        "bytes": 4 * 12 + 8 * 14 + 4 * 5, "ops": 5 + 2 * 9}


def _dense_counts(m):
    """The same counts from the definitions, on a dense mask."""
    n = m["n"]
    a = np.zeros((n, n), bool)
    for r in range(n):
        a[r, m["indices"][m["indptr"][r]:m["indptr"][r + 1]]] = True
    f = a.copy()
    for i in range(n):
        for j in range(n):
            f[i, j] |= any(a[i, h] and a[h, j] for h in range(min(i, j)))
    ups = sum(int(f[i, t]) for i in range(n) for h in range(i) if f[i, h]
              for t in range(h + 1, n) if f[h, t])
    return n, int(a.sum()), int(f.sum()), int(np.tril(f, -1).sum()), ups


@pytest.mark.parametrize("n", [12, 30])
def test_matgen_matches_the_definition(n):
    m = matgen.generate({"n": n, "per_row": 4, "margin": 1.0, "pattern_seed": n},
                        np.random.default_rng(n))
    assert _counts(m) == _dense_counts(m)


def test_counts_ignore_the_plan_layout():
    """The program's plans pad the same pattern to different shapes; the
    counts read the pattern's entries only, so they do not move."""
    from repro.core.factor_plan import build_factor_plan
    from repro.core.sparse import CSRMatrix, ELLMatrix
    from repro.core.symbolic import pilu1_symbolic

    m = poisson2d.generate({"nx": 6}, np.random.default_rng(0))
    a = CSRMatrix(n=m["n"], indptr=m["indptr"], indices=m["indices"], data=m["data"])
    pat = pilu1_symbolic(a)
    tight = ELLMatrix.from_pattern(pat, a, pad_rows_to=1)
    padded = ELLMatrix.from_pattern(pat, a, pad_rows_to=64)
    plan = build_factor_plan(a, pat)
    assert tight.vals.size != padded.vals.size != plan.n_rounds * plan.max_ops * plan.width
    n, nnz_a, nnz_f, nnz_l, ups = _counts(m)
    assert (nnz_f, nnz_l) == (pat.nnz, plan.n_ops)
    before = (work.spmv(n, nnz_a), work.sweep(n, nnz_f), work.factor(n, nnz_a, nnz_f, nnz_l, ups))
    assert before == (work.spmv(n, nnz_a), work.sweep(pat.n, pat.nnz),
                      work.factor(n, a.nnz, pat.nnz, plan.n_ops, ups))


def test_roofline_share_names_its_bound():
    from bench.peaks import lookup

    peak = lookup("TPU v5 lite")
    share, bound = work.roofline_share({"bytes": 819e6, "ops": 1.0}, 0.01, peak)
    assert bound == "bytes" and share == pytest.approx(10.0)
    with pytest.raises(KeyError):
        lookup("TPU v99")
