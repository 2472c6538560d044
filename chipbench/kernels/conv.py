"""A 1D 'same' convolution of one call: operations and bytes from its shapes.

Operations count 2 per multiply-add.  Bytes are what the call must move at
least: its input activations, weights and output activations, once each.
"""


def ops(batch: int, length: int, k: int, c_in: int, c_out: int) -> int:
    return 2 * batch * length * k * c_in * c_out


def bytes_moved(batch: int, length: int, k: int, c_in: int, c_out: int,
                in_bytes: int = 1, w_bytes: int = 1, out_bytes: int = 4) -> int:
    return (batch * length * c_in * in_bytes + k * c_in * c_out * w_bytes
            + batch * length * c_out * out_bytes)
