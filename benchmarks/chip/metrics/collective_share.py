"""``collective_share.*``: chip 0's device self time in cross-chip
collective ops inside the traced runs of the train step
(``jit_train_step`` on ``XLA Modules``), over chip 0's busy time there,
in %.

An op is a collective where its short name is one (``all-reduce``,
``all-gather``, ``reduce-scatter``, ``collective-permute``,
``all-to-all``, each also as ``-start`` / ``-done``; the TPU's
``async-collective-start`` / ``-done``; ``psum``, the name a
``shard_map`` psum's all-reduce keeps), or where it is a fusion that calls
a collective's computation (the TPU's reduce-scatter: a fusion calling
``%all-reduce-scatter...``, an all-reduce with a slice of it as its
root). A fusion that runs a matmul while an all-gather streams in beside
it (``%async_collective_fusion...``) is compute: its exchange is hidden,
and what it leaves exposed shows in the ``-done`` op that waits for it.

The names, as a TPU v5 lite trace of the 2x2 mesh step gives them on the
``XLA Ops`` line (each event named by its whole HLO instruction; no
``long_name`` stat is set there):

- ``%all-to-all.113 = bf16[4,512,2,4,128]{...} all-to-all(...),
  channel_id=11, replica_groups=[2,2]<=[4], dimensions={2}``
- ``%psum.72 = f32[]{...} all-reduce(...), channel_id=1,
  replica_groups={{0,2},{1,3}}, ...``
- ``%all-reduce.66 = bf16[]{...} all-reduce(...), ...``
- ``%all-gather.420 = bf16[5120,512]{...} all-gather(...), ...``
- ``%fusion.27 = f32[4,512,2560]{...} fusion(...), kind=kCustom,
  calls=%all-reduce-scatter.16``
- ``%async-collective-start.14 = (...) fusion(...)``,
  ``%async-collective-done.14 = bf16[5120,512]{...} fusion(...)``
- compute: ``%fusion.N = ... fusion(...), kind=kOutput,
  calls=%async_collective_fusion.N``"""

import re

import trace_reduce
import trace_scopes

KINDS = r"(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)"
SHORT = re.compile(rf"^({KINDS}(-start|-done)?|async-collective-(start|done)"
                   r"|psum)$")
CALLS = re.compile(rf"\bcalls=%{KINDS}")


def collective(op) -> bool:
    return bool(SHORT.match(op[0]) or CALLS.search(op[3]))


def read(rec):
    if "trace" not in rec:
        return None
    tr = rec["trace"]
    runs = [m for m in tr["modules"] if trace_scopes.TRAIN_MODULE.match(m[0])]
    inside = trace_reduce.within(tr["ops"], runs)
    if not inside:
        return None
    busy = sum(e - s for s, e in trace_reduce.merge(
        (o[1], o[1] + o[2]) for o in inside))
    kinds = trace_reduce.self_times(
        [["collective" if collective(o) else "other", o[1], o[2]]
         for o in inside])
    return 100.0 * sum(ns for k, _, ns in kinds if k == "collective") / busy
