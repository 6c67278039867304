"""Work of one Gram pass (kernel ``csr_gram``): per stored entry, read its
value and column (8 B); per document d with k_d entries on the support, the
k_d^2 products and adds of its outer product (2 k_d^2 operations); once per
pass, write the n_hat x n_hat f32 Gram.  Densifying, padding and lane
blocks do not count."""


def work(*, nnz: int, n_hat: int, sum_k2: float) -> tuple[float, float]:
    return 2.0 * sum_k2, 8.0 * nnz + 4.0 * n_hat * n_hat
