"""A 1D convolution with a stride, padding and groups, of one call:
operations and bytes from its shapes.

``l_out`` is the number of output frames the call keeps (``out_len`` gives
the count a 'valid' or padded conv yields).  Operations count 2 per
multiply-add.  Bytes are what the call must move at least: its input
activations, weights and output activations, once each.
"""


def out_len(l_in: int, k: int, stride: int = 1, pad: int = 0) -> int:
    return (l_in + 2 * pad - k) // stride + 1


def ops(batch: int, l_out: int, k: int, c_in: int, c_out: int, groups: int = 1) -> int:
    return 2 * batch * l_out * k * (c_in // groups) * c_out


def bytes_moved(batch: int, l_in: int, l_out: int, k: int, c_in: int, c_out: int,
                groups: int = 1, in_bytes: int = 2, w_bytes: int = 2, out_bytes: int = 4) -> int:
    return (batch * l_in * c_in * in_bytes + k * (c_in // groups) * c_out * w_bytes
            + batch * l_out * c_out * out_bytes)
