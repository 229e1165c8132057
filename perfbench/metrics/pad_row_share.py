"""pad_row_share — dispatch: padded rows over all rows packed into the batch ring."""

from benchlib import observe


def read(obs):
    return observe.pad_row_share(obs)
