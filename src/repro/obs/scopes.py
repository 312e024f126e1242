"""Name a compiled program's device operations by the scope they ran in.

The engines wrap the parts of a superstep in ``jax.named_scope`` (see
:data:`SCOPES`). The scope reaches the compiled HLO as each
instruction's ``metadata={op_name=".../sweep_gather_values/gather"}``,
and a fusion carries its root's. A profiler trace names a device
operation only by its instruction text (``%fusion.7 = f32[16384]{0} …``)
and carries no metadata, so :func:`op_scopes` reads the compiled text
and maps each instruction, keyed by its name and result shape as the
trace event prints them (:func:`op_key`), to the innermost scope of
:data:`SCOPES` in its ``op_name`` — :data:`UNSCOPED` when there is none.
"""
from __future__ import annotations

import re

import jax

# the named scopes of the fused chunk, innermost first where they nest
SCOPES = (
    "sweep_gather_values",  # source values (or messages) of a chunk
    "sweep_gather_aux",  # the sources' aux values, where gathered per edge
    "sweep_src_map",  # edge_map once per vertex, where w is unread
    "sweep_gather_rows",  # the chunk's tile rows (src, dst, w, valid, cov)
    "sweep_fold",  # the segmented combine (Pallas call or XLA scatter)
    "sweep_apply",  # the vertex update on the block's aggregate
    "sweep_hot",  # the rest of the hot (sequential) sweep
    "sweep_cold",  # the rest of the cold (snapshot) sweep
    "select",  # the device schedule pick
    "account",  # per-superstep schedule counts
    "post",  # the staleness coupling bump and calm counters
    "converge",  # the convergence test
    "chunk_loop",  # the chunk's own loop control (superstep count, stop)
)
# named scopes of the programs the out-of-core tier runs between chunks
TIER_SCOPES = (
    "ooc_place",  # pool writes: block placement and compaction moves
)
UNSCOPED = "unscoped"


def gather(scope: str, a, idx):
    """``a[idx]`` traced under the named scope ``scope``, which then names
    the gather's device operations."""
    with jax.named_scope(scope):
        return a[idx]


_INSTR = re.compile(r"\s*(?:ROOT\s+)?(%[\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=(%[\w.\-]+)")
_OPERAND = re.compile(r"%[\w.\-]+")
# computations an instruction runs as device operations of their own
_RUNS = re.compile(r"(?:(?:body|condition|true_computation|false_computation)"
                   r"=%[\w.\-]+|branch_computations=\{[^}]*\})")
_TO_APPLY = re.compile(r"to_apply=%[\w.\-]+")


def op_key(text: str) -> str | None:
    """``%fusion.7 = f32[16384]{0:T(1024)}`` from an instruction's text
    (a compiled HLO line or a trace event's name): its name and result
    shape, a tuple shape whole."""
    m = _INSTR.match(text)
    if m is None:
        return None
    i = m.end()
    if text.startswith("(", i):
        depth = 0
        for j in range(i, len(text)):
            depth += {"(": 1, ")": -1}.get(text[j], 0)
            if depth == 0:
                return f"{m.group(1)} = {text[i:j + 1]}"
        return None
    j = text.find(" ", i)
    return f"{m.group(1)} = {text[i:j if j >= 0 else len(text)]}"


def _operands(line: str) -> list[str]:
    """Operand names of an instruction line: the names inside the
    parentheses after its opcode."""
    key = op_key(line)
    at = line.find("(", line.find(key) + len(key)) if key else -1
    if at < 0:
        return []
    depth = 0
    for j in range(at, len(line)):
        depth += {"(": 1, ")": -1}.get(line[j], 0)
        if depth == 0:
            return _OPERAND.findall(line[at:j])
    return []


def scope_of(op_name: str) -> str:
    """The innermost of :data:`SCOPES` (or :data:`TIER_SCOPES`) on an
    ``op_name`` path; a scope under a transform reads
    ``vmap(sweep_fold)`` and counts as well."""
    for part in reversed(op_name.split("/")):
        name = part[part.rfind("(") + 1:].rstrip(")")
        if name in SCOPES or name in TIER_SCOPES:
            return name
    return UNSCOPED


def op_scopes(hlo_text: str) -> dict[str, str]:
    """Key (:func:`op_key`) -> scope of every instruction that runs as a
    device operation of one compiled module: those of the entry
    computation and of the loop bodies, conditions, branches and calls
    reached from it (not the insides of fusions, reducers or
    comparators). An instruction with no ``op_name`` takes its fused
    computation's root's, and one with none either (a multi-output
    fusion's ``tuple``) the nearest named operand's; one with no named
    operand (in a loop the compiler made) that of the instruction that
    runs its computation."""
    comps: dict[str, list[tuple[str, str]]] = {}  # name -> [(line, key)]
    roots: dict[str, str] = {}
    entry = cur = None
    for line in hlo_text.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            head = line.split()
            cur = head[1] if head[0] == "ENTRY" else head[0]
            entry = cur if head[0] == "ENTRY" else entry
            comps[cur] = []
            continue
        key = op_key(line) if cur is not None else None
        if key is None:
            continue
        comps[cur].append((line, key))
        if line.lstrip().startswith("ROOT "):
            roots[cur] = line
    by_name = {key.split(" = ", 1)[0]: line
               for body in comps.values() for line, key in body}

    def resolve(line: str) -> str | None:
        # breadth first from the instruction: into a fusion's root, else
        # through the operands; the nearest op_name decides
        seen, level = set(), [line]
        while level:
            nxt = []
            for ln in level:
                named = _OP_NAME.search(ln)
                if named:
                    return scope_of(named.group(1))
                called = _CALLS.search(ln)
                if called and called.group(1) in roots:
                    nxt.append(roots[called.group(1)])
                    continue
                for a in _operands(ln):
                    if a in by_name and a not in seen:
                        seen.add(a)
                        nxt.append(by_name[a])
            level = nxt
        return None

    out: dict[str, str] = {}
    todo, done = [(entry, UNSCOPED)], set()
    while todo:
        comp, outer = todo.pop()
        if comp in done or comp not in comps:
            continue
        done.add(comp)
        for line, key in comps[comp]:
            scope = resolve(line)
            out[key] = outer if scope is None else scope
            runs = _RUNS.findall(line)
            if " call(" in line:
                runs += _TO_APPLY.findall(line)
            todo += [(c, out[key]) for r in runs
                     for c in _OPERAND.findall(r)]
    return out


def merge(maps) -> dict[str, str]:
    """One map from several modules' (the dispatch buckets'): a key that
    two of them name differently is :data:`UNSCOPED`."""
    out: dict[str, str] = {}
    for m in maps:
        for key, scope in m.items():
            out[key] = scope if out.get(key, scope) == scope else UNSCOPED
    return out
