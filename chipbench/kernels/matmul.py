"""A (M, K) x (K, N) matrix product of one call: operations and bytes from its shapes.

Operations count 2 per multiply-add.  Bytes are the operands and the result,
once each.
"""


def ops(m: int, k: int, n: int) -> int:
    return 2 * m * k * n


def bytes_moved(m: int, k: int, n: int, in_bytes: int = 1, w_bytes: int = 1,
                out_bytes: int = 4) -> int:
    return m * k * in_bytes + k * n * w_bytes + m * n * out_bytes
