"""Share of its roofline the state-space scan reaches: the least time the
chip's published peaks allow for what the recurrence requires
(``harness.nemotron_h_parts.scan_train_required``: three ``P x N`` products
a head a token forward, the backward pass counted the same way; x, dt, B, C
in and y out and their gradients, once each) over ``ssm_scan_ms``.  The
same work whatever implements the scan: the chunk algebra's own products,
its masks and the chunk states that cross HBM are not required work, so
they lower the share."""

from benchmarks.harness import nemotron_h_parts as parts


def read(run):
    return parts.scan_roofline(run)
