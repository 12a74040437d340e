"""Operations and bytes that the work *requires*, from shapes alone.

A multiply-add is two operations everywhere.  Nothing here asks the
compiler (``cost_analysis`` cannot see inside a Mosaic kernel and counts
what XLA issues, not what the model needs).
"""

from __future__ import annotations

from typing import List, NamedTuple, Tuple

# -- transformers -----------------------------------------------------------


def gpt2_matmul_params(cfg: dict) -> int:
    """Parameters that a token multiplies: the blocks, the final norm and
    the tied embedding (38.6 M of GPT-2-small's 123.65 M, counted once:
    it is the output head).  The position table is looked up, not
    multiplied, and is left out, so the count does not grow with the
    sequence."""
    d, m, v = cfg["n_embd"], cfg["n_inner"], cfg["vocab_size"]
    block = (4 * d * d + 4 * d) + (2 * d * m + m + d) + 4 * d
    return cfg["n_layer"] * block + 2 * d + v * d


def transformer_train_flops_per_token(n_params: int, n_layer: int,
                                      d_model: int, seq_len: int, *,
                                      causal: bool) -> float:
    """Forward and backward: 6 per parameter, plus the attention scores and
    values, 12·L·s·d per token, of which a causal model needs half."""
    attention = 12.0 * n_layer * seq_len * d_model
    if causal:
        attention /= 2.0
    return 6.0 * n_params + attention


def flash_train_required(batch: int, heads: int, seq: int, head_dim: int,
                         *, causal: bool, layers: int,
                         bytes_per_element: int = 2) -> Tuple[float, float]:
    """(operations, bytes) one training step's attention kernels need over
    ``layers`` layers.  Forward: QK^T and PV.  Backward: the scores again
    (only their row sums were kept), dP, dV, dK and dQ — five products,
    however many kernels share them out.  Seven products of
    2·b·h·s²·d each, halved under a causal mask.  Bytes: forward reads q,
    k, v and writes o and the float32 row statistics; backward reads q, k,
    v, o, do and the statistics and writes dq, dk, dv."""
    product = 2.0 * batch * heads * seq * seq * head_dim
    if causal:
        product /= 2.0
    tensor = batch * heads * seq * head_dim * bytes_per_element
    rows = batch * heads * seq * 4
    flops = 7.0 * product
    nbytes = (4 * tensor + rows) + (8 * tensor + 2 * rows)
    return layers * flops, layers * float(nbytes)


# -- ResNet-50 --------------------------------------------------------------


class Conv(NamedTuple):
    out_hw: int        # output height = width
    kernel: int        # kernel height = width
    cin: int
    cout: int
    in_hw: int
    input_grad: bool   # False for the stem: nothing upstream wants it

    @property
    def macs(self) -> int:
        """Multiply-adds of one forward pass over one image."""
        return self.out_hw ** 2 * self.kernel ** 2 * self.cin * self.cout


def resnet50_convs(cfg: dict, image: int) -> List[Conv]:
    """The 53 convolutions of ResNet-50 v1.5 (stride of a down-sampling
    block on its 3x3), in forward order."""
    f0 = cfg["num_filters"]
    hw = image // 2
    convs = [Conv(hw, 7, 3, f0, image, False)]
    hw //= 2  # 3x3 max pool, stride 2
    cin = f0
    for stage, count in enumerate(cfg["stage_sizes"]):
        f = f0 * 2 ** stage
        for j in range(count):
            stride = 2 if stage > 0 and j == 0 else 1
            out = hw // stride
            convs.append(Conv(hw, 1, cin, f, hw, True))
            convs.append(Conv(out, 3, f, f, hw, True))
            convs.append(Conv(out, 1, f, 4 * f, out, True))
            if cin != 4 * f:
                convs.append(Conv(out, 1, cin, 4 * f, hw, True))
            cin, hw = 4 * f, out
    return convs


def resnet50_forward_macs(cfg: dict, image: int) -> int:
    """Multiply-adds of one image's forward pass: convolutions and the
    classifier."""
    f0 = cfg["num_filters"]
    return sum(c.macs for c in resnet50_convs(cfg, image)) \
        + 8 * 4 * f0 * cfg["num_classes"]


def resnet50_train_flops_per_image(cfg: dict, image: int) -> float:
    """Forward, gradient to the input and gradient to the weights of every
    convolution and of the classifier, 2 operations per multiply-add; the
    stem has no input gradient."""
    convs = resnet50_convs(cfg, image)
    dense = 8 * 4 * cfg["num_filters"] * cfg["num_classes"]
    macs = sum(c.macs * (3 if c.input_grad else 2) for c in convs) \
        + 3 * dense
    return 2.0 * macs


def conv_train_required(convs: List[Conv], batch: int,
                        bytes_per_element: int = 2) -> Tuple[float, float]:
    """(operations, bytes) one training step's convolutions need: each pass
    reads its two operands once and writes its result once, activations at
    ``bytes_per_element``, weight gradients in float32."""
    flops = nbytes = 0.0
    for c in convs:
        x = batch * c.in_hw ** 2 * c.cin * bytes_per_element
        y = batch * c.out_hw ** 2 * c.cout * bytes_per_element
        w = c.kernel ** 2 * c.cin * c.cout
        flops += 2.0 * batch * c.macs * (3 if c.input_grad else 2)
        nbytes += (x + w * bytes_per_element + y)      # forward
        nbytes += (x + y + w * 4)                      # weight gradient
        if c.input_grad:
            nbytes += (y + w * bytes_per_element + x)  # input gradient
    return flops, nbytes


def least_seconds(flops: float, nbytes: float, peak) -> Tuple[float, str]:
    """The roofline: the least time the chip could take, and which peak
    sets it."""
    compute, memory = flops / peak.flops, nbytes / peak.hbm_bytes
    return (compute, "compute") if compute >= memory else (memory, "memory")
