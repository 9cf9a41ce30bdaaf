"""Reads the program scopes (``repro.telemetry``) out of an optimized HLO
text, for the tests that check the programs carry them."""
import re

PROGRAM_SCOPE = re.compile(r"\b(?:llm|qfl|nm|tape|model)\.[a-z_]+")
# free of device work, or control flow whose bodies are counted
_FREE = {"parameter", "constant", "tuple", "get-tuple-element", "bitcast",
         "while", "conditional", "call", "after-all"}


def instructions(text):
    """(computation, opcode, op_name) of each instruction a device runs
    as an operation of its own: those of fused computations and reducers
    (run inside their caller) and free ones are left out."""
    comp, rows, inner = None, [], set()
    for line in text.splitlines():
        if line and not line[0].isspace():
            if line.rstrip().endswith("{"):
                comp = re.match(r"^(?:ENTRY )?%?([\w.\-]+)", line).group(1)
            continue
        m = re.match(r"^\s+(?:ROOT )?%[\w.\-]+ = (.*)$", line)
        if not m:
            continue
        inner.update(re.findall(r"(?:to_apply|calls)=%([\w.\-]+)", m[1]))
        op = re.search(r"\s([a-z][\w\-]*)\(", " " + m[1])
        name = re.search(r'op_name="([^"]*)"', m[1])
        rows.append((comp, op[1] if op else "", name[1] if name else ""))
    return [r for r in rows if r[0] not in inner and r[1] not in _FREE]


def op_names(text):
    return [op for _, _, op in instructions(text)]


def scopes(text):
    """Every program scope an instruction's op_name names."""
    return {s for op in op_names(text) for s in PROGRAM_SCOPE.findall(op)}


def unscoped_share(text):
    """The share of the operations that JAX named (an op_name) with no
    program scope, neither their own nor one all the scoped operations of
    their computation share."""
    rows = [r for r in instructions(text) if r[2]]
    shared = {}
    for comp, _, op in rows:
        s = tuple(PROGRAM_SCOPE.findall(op))
        if s:
            prev = shared.get(comp, s)
            n = 0
            while n < min(len(prev), len(s)) and prev[n] == s[n]:
                n += 1
            shared[comp] = s[:n]
    bare = [r for r in rows if not PROGRAM_SCOPE.search(r[2])
            and not shared.get(r[0])]
    return len(bare) / max(len(rows), 1)
