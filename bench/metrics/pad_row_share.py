"""Share of decode-batch rows that are padding (layer: store host prep):
1 - decoded strings / padded rows, from the stats RPC's deltas over the
window. None where no batch ran."""


def read(ctx):
    c = ctx["counters"]
    if not c["padded_rows"]:
        return None
    return 100.0 * (1.0 - c["decoded_strings"] / c["padded_rows"])
