"""Share of their roofline the sparse attention kernels reach, in
percent: the least time attention over the pairs the selection KEPT
could take, forward and backward — for each the larger of its FLOPs
over the bf16 peak and its bytes over the HBM peak
(``benchmark/sparse_attention.py``, ``benchmark/peaks.json``) — over
the device time the trace shows in ``%sparse_attn_*.<n>``
(``sparse_attn_ms``).

Pairs come from the program's own counter: the ``sparse.select`` events
the trainer journals where it pulls its statistics (per sparse layer
the keys selected in a step, summed over its micro-batches). The count
is of the work the model defines, so it reads the same whatever
implements it: a kernel that multiplies every causal pair and masks
(``path="causal_tiles_masked"``, 4.3 x the pairs at 16k rows) pays for
them in the time and earns nothing, the second pass over the
probabilities that the indexer's loss needs earns nothing, and the
share cannot pass 100."""

import re

UNIT = "%"
LAYER = "kernels"
SOURCE = "device_trace"
MOVES = "tokens_per_s"
PATTERN = re.compile(
    r'^%[\w\-]*sparse_attn[\w\-]*[.\d]* = .*custom_call_target="tpu_custom_call"'
)


def read(trace, spans, record, select_events=None):
    from benchmark import sparse_attention

    def least(sizes, peak, events):
        return sum(
            sparse_attention.attention_least_seconds(
                pairs, tokens,
                sizes["num_attention_heads"], sizes["num_key_value_heads"],
                sizes["head_dim"], peak,
            )
            for pairs, tokens in zip(
                sparse_attention.mean_per_layer(events, "keys_selected"),
                sparse_attention.mean_per_layer(events, "queries"),
            )
        )

    return sparse_attention.roofline_share(
        trace, record, PATTERN, select_events, least
    )
