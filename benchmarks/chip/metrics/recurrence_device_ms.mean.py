"""Device time of the served forward's two recurrences per traced
execution of the forward: the ops whose compiled-HLO ``op_name`` lies in
the named scope ``interest_extractor`` (DIEN's GRU) or
``interest_evolution`` (its AUGRU), summed, over the executions in the
trace.  On the TPU each recurrence is one Pallas kernel in its scope, with
the padding of its operands around it; elsewhere it is a scan.

The scope tables come from ``program.forward_lowered``, compiled as
``program.forward_scope_tables`` compiles them (the process's caches
dropped, the metadata in the compile cache's key); ``program.SCOPES``
names only the outer scopes, in which both scans lie in ``interaction``,
so this reader matches its own.  A scan is a while loop: where the trace
shows the ops of its body, the loop's own event, which spans them, is left
out; where it shows only the loop, the loop is counted.  A program with
no such scope reads nothing."""
import jax

import program

SCOPES = ("interest_extractor", "interest_evolution")


def table(hlo: str) -> dict[str, str]:
    """``%name = shape`` → ``loop`` (a while) or ``op``, for every
    instruction of compiled HLO text whose metadata puts it in a scan."""
    out = {}
    for line in hlo.splitlines():
        m = program._OP_NAME.search(line)
        if m and line.lstrip().startswith(("%", "ROOT %")) \
                and set(m.group(1).split("/")) & set(SCOPES):
            out[program._op_key(line)] = "loop" if " while(" in line else "op"
    return out


def tables(run, buckets) -> dict[int, dict[str, str]]:
    jax.clear_caches()
    key = "jax_compilation_cache_include_metadata_in_key"
    was = getattr(jax.config, key)
    jax.config.update(key, True)
    try:
        return {b: table(low.compile().as_text())
                for b, low in program.forward_lowered(run, buckets).items()}
    finally:
        jax.config.update(key, was)


def recurrence_ns(run) -> float | None:
    """Device nanoseconds of the scans' ops over the whole trace."""
    p = run.window.profile
    if p is None or not p.forward or not p.op_ns:
        return None
    kinds = program.op_scopes(
        p.op_ns, tables(run, program.traced_buckets(p)).values())
    ns = {"op": 0.0, "loop": 0.0}
    for name, t in p.op_ns.items():
        if kinds[name] in ns:
            ns[kinds[name]] += t
    return ns["op"] or ns["loop"] or None


def read(run):
    ns = recurrence_ns(run)
    if ns is None:
        return None
    return ns / len(run.window.profile.forward) * 1e-6
