"""Bytes the pool moved between the device and host tiers, both ways,
per output token of the window (``TransferStats.pairs``)."""

PAIRS = ("device->host", "host->device")


def read(ctx):
    moved = sum(ctx.transfer_bytes.get(p, 0) for p in PAIRS)
    tokens = ctx.window.tokens_inside()
    return moved / tokens if moved and tokens else None
