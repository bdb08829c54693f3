"""Share of its roofline the indexer's kernel reaches, in percent: the
least time the index scores of every CAUSAL pair could take — the
larger of their FLOPs over the bf16 peak and the bytes of qI, kI, w
and of what the selection writes over the HBM peak
(``benchmark/sparse_attention.py``, ``benchmark/peaks.json``) — over
the device time the trace shows in ``%sparse_index_select.<n>``
(``indexer_ms``). The search for the 2048th largest score is not a
matrix product and earns nothing: it costs time, so the share says how
far the selection is from costing no more than the scores.

Queries come from the program's ``sparse.select`` events (per sparse
layer the queries of a step); rows from the cell's sequence length."""

import re

UNIT = "%"
LAYER = "kernels"
SOURCE = "device_trace"
MOVES = "tokens_per_s"
PATTERN = re.compile(
    r'^%[\w\-]*sparse_index[\w\-]*[.\d]* = .*custom_call_target="tpu_custom_call"'
)


def read(trace, spans, record, select_events=None):
    from benchmark import sparse_attention

    def least(sizes, peak, events):
        seq, sa = sizes["sequence_length"], sizes["sa_config"]
        return sum(
            sparse_attention.index_least_seconds(
                tokens / seq * sparse_attention.causal_pairs(seq), tokens,
                sa["indexer_num_heads"], sa["indexer_head_dim"], peak,
            )
            for tokens in sparse_attention.mean_per_layer(events, "queries")
        )

    return sparse_attention.roofline_share(
        trace, record, PATTERN, select_events, least
    )
