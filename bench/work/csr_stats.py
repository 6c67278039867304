"""Work of one screen pass (kernel ``csr_stats``): per stored entry, read
its f32 value and i32 column (8 B) and do 3 operations (value, square,
add into the sums); once per pass, write the two f32 sums of every word.
Chunk padding and the kernel's one-hot matrix work do not count."""


def work(*, nnz: int, n: int) -> tuple[float, float]:
    """(operations, bytes) of one pass over ``nnz`` entries, ``n`` words."""
    return 3.0 * nnz, 8.0 * nnz + 2 * 4.0 * n
