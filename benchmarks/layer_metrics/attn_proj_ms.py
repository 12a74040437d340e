"""Device milliseconds a step spends on softmax attention's projections:
ops under ``hvd_attn_qkv`` (the q / k / v projections, the per-head norms,
the rotary embedding, the k / v repeat) or ``hvd_attn_out`` (the output gate
where there is one, ``o_proj``) of ``models/qwen3_next.GatedAttention`` and
``models/sdar.BlockDiffusionAttention``: first run, recompute and
transposes.  With the three ``flash_*_ms`` and ``flash_layout_ms`` it covers
``hvd_attn``.  Interval arithmetic.  Device trace."""

from benchmarks.harness import part_scopes as parts


def read(run):
    return parts.scope_ms(run, (parts.ATTN_QKV, parts.ATTN_OUT))
