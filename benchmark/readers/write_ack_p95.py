"""95th percentile of the window's per-batch acknowledgement latency, ms."""

import numpy as np


def read(ctx, layer):
    ack = ctx.window.get("ack_ms")
    if ack is None or not len(ack):
        return None
    return float(np.percentile(ack, 95))
