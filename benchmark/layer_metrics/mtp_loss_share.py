"""The multi-token-prediction module's share of the loss the cell
trains on, in percent: ``lambda x mtp / (main + lambda x mtp)``, mean
over the window's journalled whole steps — ``main`` the next-token
cross-entropy, ``mtp`` the module's against the token after the next,
``lambda`` its weight (0.1). About 9.1 (0.1 / 1.1) while both streams'
losses stand near ``ln(vocabulary)``. The module's block, projection
and head pass run whatever its loss reads, so this moves NO rate (the
entry's ``moves`` names the cell's only one); it is watched because a
module whose loss has vanished (its stream dropped, its weight lost)
or blown up means the cell no longer measures the objective it names.

From the program's own counters: ``main`` and ``mtp`` over
``micro_batches`` of the ``mtp.loss`` events the trainer journals
where it pulls its statistics (every tenth step), and ``loss_weight``
of the ``mtp.schedule`` event journalled where the loss is traced.
The events come from the program's own buffer (``benchmark/mtp.py``);
a program without them (a parent commit) reads nothing and the metric
is left out."""

UNIT = "%"
LAYER = "loss (multi-token prediction)"
SOURCE = "program_counter"
MOVES = "tokens_per_s"


def read(trace, spans, record, events=None):
    if events is None:
        from benchmark import mtp

        events = mtp.program_events("mtp.loss", "mtp.schedule")
    micro_batches = record["geometry"]["accum_steps"] + 1
    weights = [
        rec["attrs"]["loss_weight"] for rec in events
        if rec.get("name") == "mtp.schedule"
    ]
    shares = []
    for rec in events:
        attrs = rec.get("attrs", {})
        if (
            rec.get("name") != "mtp.loss"
            # (The calibration program's single micro-batch is
            # journalled too: whole steps only.)
            or attrs.get("micro_batches") != micro_batches
            or not weights
        ):
            continue
        weighted = weights[-1] * attrs["mtp"]
        if attrs["main"] + weighted > 0:
            shares.append(100.0 * weighted / (attrs["main"] + weighted))
    return sum(shares) / len(shares) if shares else None
