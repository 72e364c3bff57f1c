"""Device time of the served forward's ``embedding_gather`` scope per traced
execution of the forward: the ops whose compiled HLO metadata puts them in
the scope, summed, over the executions in the trace."""
import program


def read(run):
    p = run.window.profile
    if p is None or not p.forward:
        return None
    ns = program.scope_ns(run)
    if ns is None or "embedding_gather" not in ns:
        return None
    return ns["embedding_gather"] / len(p.forward) * 1e-6
