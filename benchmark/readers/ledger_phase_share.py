"""Share of the ledger's pod-seconds spent in the named phases.

The program's lifecycle ledger (`obs/ledger.py`) stamps every pod at the
boundaries admission, queue, encode, dispatch, fetch, commit, fanout; its
`phase_split` sums each phase over the pods completed since the window
opened. Phases of one burst are shared by its pods, so this is a relative
weight, not wall seconds."""


def read(ctx, phases):
    split = ctx["ledger"]
    total = sum(split.values())
    if total <= 0:
        return None
    return 100.0 * sum(split.get(p, 0.0) for p in phases) / total
