"""``bench/work.py`` against a hand count on a tiny CSR matrix."""
import numpy as np

from bench import work

# 3x3 tridiagonal: rows hold 2, 3 and 2 entries
INDPTR = np.array([0, 2, 5, 7])
N, NNZ = 3, 7
F32 = np.dtype("float32").itemsize


def test_trip_bytes_match_a_hand_count():
    # matrix: 7 values (4 B) + 7 column indices (4 B) + 4 row pointers
    # (4 B) = 28 + 28 + 16 = 72; vectors x, r, p, Ap: 4 * 3 rows * 4 B
    assert work.trip_bytes(N, NNZ, 1, F32) == 72 + 48
    assert work.trip_bytes(N, NNZ, 4, F32) == 72 + 4 * 48
    # float64 values: 7 * 8 + 28 + 16 = 100; vectors 4 * 3 * 8 = 96
    assert work.trip_bytes(N, NNZ, 1, 8) == 100 + 96


def test_matrix_term_does_not_grow_with_width():
    assert work.matrix_bytes(N, NNZ, F32) == 72 == (
        work.trip_bytes(N, NNZ, 4, F32) - work.vector_bytes(N, 4, F32))
    assert work.vector_bytes(N, 4, F32) == 4 * work.vector_bytes(N, 1, F32)
    assert INDPTR[-1] == NNZ and len(INDPTR) == N + 1
