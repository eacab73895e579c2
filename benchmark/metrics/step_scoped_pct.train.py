"""Share of the traced slice's busy time whose operation is in the program's
own table of its step (``observability.programs.op_scopes``) and under a scope
path there: what the other readers of ``benchmark/parts.py`` can be trusted
for. The operations under no path go to stderr by name."""

from benchmark import parts


def read(run):
    return parts.scoped_pct(run)
