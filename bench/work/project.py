"""Work of the serving projection (kernel ``project``): per answered
document, gather the P packed loading slots' counts and multiply-add them
(2 P operations), reading 4 B per slot and writing k f32 scores.  Rows that
only pad the batch do not count."""


def work(*, rows: int, slots: int, k: int) -> tuple[float, float]:
    return 2.0 * rows * slots, 4.0 * rows * slots + 4.0 * rows * k
