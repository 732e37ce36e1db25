"""The sharded FFM cell's arithmetic: the bytes one chunk's exchange must
move between chips, computed from what the chunk holds (``arith.py`` is
left as it is; a PR that adds a cell adds a file).

A member's rows touch some distinct features that another member owns.
Whatever implements the exchange, each of those features' parameters
(its ``n_fields`` vectors of ``k`` and its linear weight) has to reach
the member once, and their gradient has to go back once. Padding to a
block's width, the capacity of a buffer and rows sent masked are the
implementation's and are not counted.
"""

from __future__ import annotations


def block_values(n_fields: int, k: int) -> int:
    """Values a feature owns under SGD: its vectors and its weight."""
    return n_fields * k + 1


def exchange_bytes_a_chip(remote_blocks: float, chips: int, n_fields: int,
                          k: int) -> float:
    """Least bytes a chip must SEND for chunks whose members hold
    ``remote_blocks`` distinct features they do not own (summed over the
    members): as a requester the gradients of its share of them, as an
    owner the parameters of as many on average, f32."""
    return 2.0 * remote_blocks / chips * block_values(n_fields, k) * 4
