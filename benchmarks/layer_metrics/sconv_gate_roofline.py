"""Share of their roofline the gated short convolution's gates and taps
reach: the least time the chip's published bandwidth allows for what they
require (``harness.lfm2_parts.sconv_gate_train_required``: B, C and x in and
``y = C * z`` out forward; dy, B, x and C in and dB, dC and dx out backward;
once each, at the compute dtype) over ``sconv_gate_ms``.  The same bytes
whatever implements them: ``B * x`` or ``z`` written to HBM between passes,
an unfused pass or a second forward run are time and not required traffic,
so they lower the share."""

from benchmarks.harness import lfm2_parts as parts


def read(run):
    return parts.sconv_gate_roofline(run)
