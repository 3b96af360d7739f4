"""The HBM bytes a batch of the slasher engine has to move, whatever
implements it: each distinct validator row the batch touches is read
once and written once, in all three planes (min and max targets, int32,
and occupancy, one byte), across the whole history ring."""

PLANE_BYTES_PER_EPOCH = 4 + 4 + 1


def plane_bytes(rows: int, history: int) -> int:
    """Bytes read and written for ``rows`` distinct validator rows of
    ``history`` epochs: one gather and one scatter of each."""
    return rows * history * PLANE_BYTES_PER_EPOCH * 2
