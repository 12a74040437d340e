"""Share of their roofline the grouped products of non-gated relu^2 experts
reach: the least time the chip's published peaks allow for the assignments
an even router sends to the held experts through an expert's *two* matrices
(``n_routed_experts`` held of ``router_num_experts``), forward and
backward, and those experts' weights read once a pass
(``harness.nemotron_h_parts.experts_train_required``) over the time of the
ops under ``hvd_moe_experts``.  A tile's empty rows and the forward products
computed again in the backward loop are not required work, so they lower
the share."""

from benchmarks.harness import nemotron_h_parts as parts


def read(run):
    return parts.experts_roofline(run)
