"""An independent byte-level checker of the benchmark's JSON grammars.

It shares no code with the program's Earley checker.  It reads the
grammars as JSON with schemas, down to the program's own terminal
definitions: STRING ``"([^"\\\\]|\\\\(["\\\\/bfnrt]|u[0-9a-fA-F]{4}))*"``
(any byte but ``"`` and ``\\``, or an escape), NUMBER
``-?(0|[1-9][0-9]*)(\\.[0-9]+)?([eE][-+]?[0-9]+)?`` (a lone digit too),
``true``, ``false``, ``null``, and whitespace ``[ \\t\\n\\r]+`` allowed
between any two terminals and at either end.  Keys and enumerated
values of a schema are literal terminals.

``extend(state, data)`` returns the state after the bytes ``data``, or
None when no sentence of the grammar starts with what was read: the
same question a DOMINO mask answers for a token, with unbounded
lookahead.  ``complete(state)`` says whether what was read is a whole
sentence, which is when end of sequence is legal.
"""
from __future__ import annotations

from typing import Optional, Tuple

ANY = ("any",)
STR = ("str",)
NUM = ("num",)

SCHEMAS = {
    "json": ANY,
    "json_gsm8k": ("obj", (
        ("thoughts", ("arr", ("obj", (("step", STR),
                                      ("calculation", STR),
                                      ("result", NUM))), 1)),
        ("answer", NUM))),
    "json_conll": ("obj", (
        ("entities", ("arr", ("obj", (
            ("text", STR),
            ("type", ("enum", ("PER", "ORG", "LOC", "MISC"))))), 0)),)),
}

_WS = b" \t\n\r"
_DIGITS = b"0123456789"
_HEX = b"0123456789abcdefABCDEF"
_ESC = b'"\\/bfnrt'
# NUMBER as a DFA: state -> (next state per byte class); accepting states
_NUM_ACCEPT = frozenset(("zero", "int", "frac", "exp"))


def _num_step(st: str, c: int) -> Optional[str]:
    d = c in _DIGITS
    if st == "start":
        return "minus" if c == 45 else ("zero" if c == 48 else
                                        "int" if d else None)
    if st == "minus":
        return "zero" if c == 48 else ("int" if d else None)
    if st in ("zero", "int"):
        if st == "int" and d:
            return "int"
        if c == 46:
            return "dot"
        return "e" if c in b"eE" else None
    if st in ("dot", "frac"):
        if d:
            return "frac"
        return "e" if st == "frac" and c in b"eE" else None
    if st == "e":
        return "esign" if c in b"+-" else ("exp" if d else None)
    if st in ("esign", "exp"):
        return "exp" if d else None
    return None


# A state is (stack, lex).  The stack holds frames, innermost last:
#   ("val", node)                      a value of `node` is expected
#   ("obj", None, phase)               free object: key_or_end|key|colon|after
#   ("obj", fields, i, phase)          schema object at field i: key|colon|after
#   ("arr", item, min, n, phase)       array: first|after|next
#   ("done",)                          the top value is complete
# lex is None between terminals, or the terminal being read:
#   ("str", role, esc)                 free string; esc 0, 1 (after \),
#                                      2..5 (hex digits of \u still owed)
#   ("lit", role, candidates, pos)     literal strings (keys, enums)
#   ("num", dfa_state)
#   ("kw", rest)                       rest of true/false/null
# role is "key" or "value".
State = Tuple[tuple, Optional[tuple]]


def start(grammar: str) -> State:
    return ((("val", SCHEMAS[grammar]),), None)


def _value_done(stack: tuple) -> tuple:
    """Pop the finished value's frame and advance its container."""
    stack = stack[:-1]
    if not stack:
        return (("done",),)
    top = stack[-1]
    if top[0] == "obj" and top[1] is None:
        return stack[:-1] + (("obj", None, "after"),)
    if top[0] == "obj":
        return stack[:-1] + (("obj", top[1], top[2] + 1, "after"),)
    if top[0] == "arr":
        return stack[:-1] + (("arr", top[1], top[2], top[3] + 1, "after"),)
    raise AssertionError(top)


def _container_done(stack: tuple) -> tuple:
    """The container on top closed: it is the value its parent awaited."""
    return _value_done(stack[:-1] + (("val", None),))


def _lex_done(stack: tuple, role: str) -> tuple:
    if role == "value":
        return _value_done(stack)
    top = stack[-1]                           # a key: now expect ':'
    if top[1] is None:
        return stack[:-1] + (("obj", None, "colon"),)
    return stack[:-1] + (("obj", top[1], top[2], "colon"),)


def _start_value(stack: tuple, node: tuple, c: int) -> Optional[State]:
    """``c`` begins a value of ``node``; ``stack`` ends in its val frame."""
    kind = node[0]
    if kind in ("any", "obj") and c == 123:                    # {
        frame = (("obj", None, "key_or_end") if kind == "any"
                 else ("obj", node[1], 0, "key"))
        return stack[:-1] + (frame,), None
    if kind in ("any", "arr") and c == 91:                     # [
        item, lo = (ANY, 0) if kind == "any" else (node[1], node[2])
        return stack[:-1] + (("arr", item, lo, 0, "first"),), None
    if kind in ("any", "str") and c == 34:                     # "
        return stack, ("str", "value", 0)
    if kind == "enum" and c == 34:
        return stack, ("lit", "value",
                       tuple('"%s"' % v for v in node[1]), 1)
    if kind in ("any", "num"):
        st = _num_step("start", c)
        if st is not None:
            return stack, ("num", st)
    if kind == "any":
        for kw in (b"true", b"false", b"null"):
            if c == kw[0]:
                return stack, ("kw", kw[1:])
    return None


def step(state: State, c: int) -> Optional[State]:
    stack, lex = state
    if lex is not None:
        kind = lex[0]
        if kind == "str":
            esc = lex[2]
            if esc == 0:
                if c == 34:
                    return _lex_done(stack, lex[1]), None
                return stack, (("str", lex[1], 1) if c == 92 else lex)
            if esc == 1:
                if c in _ESC:
                    return stack, ("str", lex[1], 0)
                return (stack, ("str", lex[1], 2)) if c == 117 else None
            if c not in _HEX:
                return None
            return stack, ("str", lex[1], 0 if esc == 5 else esc + 1)
        if kind == "lit":
            pos = lex[3]
            cands = tuple(s for s in lex[2] if len(s) > pos
                          and ord(s[pos]) == c)
            if not cands:
                return None
            if len(cands[0]) == pos + 1:              # closing quote read
                return _lex_done(stack, lex[1]), None
            return stack, ("lit", lex[1], cands, pos + 1)
        if kind == "num":
            st = _num_step(lex[1], c)
            if st is not None:
                return stack, ("num", st)
            if lex[1] not in _NUM_ACCEPT:
                return None
            return step((_value_done(stack), None), c)
        if kind == "kw":
            if c != lex[1][0]:
                return None
            rest = lex[1][1:]
            return (_value_done(stack), None) if not rest else (
                stack, ("kw", rest))
    if c in _WS:
        return state
    top = stack[-1]
    tag = top[0]
    if tag == "done":
        return None
    if tag == "val":
        return _start_value(stack, top[1], c)
    if tag == "arr":
        item, lo, n, phase = top[1:]
        if phase == "after":
            if c == 44:                                        # ,
                return stack[:-1] + (("arr", item, lo, n, "next"),), None
            return (_container_done(stack), None) if c == 93 else None
        if phase == "first" and c == 93 and lo == 0:           # ]
            return _container_done(stack), None
        return _start_value(stack + (("val", item),), item, c)
    fields, phase = top[1], top[-1]
    if fields is None:                                         # free object
        if phase == "key_or_end" and c == 125:                 # }
            return _container_done(stack), None
        if phase in ("key_or_end", "key") and c == 34:
            return stack, ("str", "key", 0)
        if phase == "colon" and c == 58:                       # :
            return stack[:-1] + (("obj", None, "value"),
                                 ("val", ANY)), None
        if phase == "after":
            if c == 44:
                return stack[:-1] + (("obj", None, "key"),), None
            if c == 125:
                return _container_done(stack), None
        return None
    i = top[2]
    if phase == "key" and c == 34:
        return stack, ("lit", "key", ('"%s"' % fields[i][0],), 1)
    if phase == "colon" and c == 58:
        return stack[:-1] + (("obj", fields, i, "value"),
                             ("val", fields[i][1])), None
    if phase == "after":
        if i < len(fields) and c == 44:
            return stack[:-1] + (("obj", fields, i, "key"),), None
        if i == len(fields) and c == 125:
            return _container_done(stack), None
    return None


def extend(state: Optional[State], data: bytes) -> Optional[State]:
    for c in data:
        if state is None:
            return None
        state = step(state, c)
    return state


def complete(state: Optional[State]) -> bool:
    if state is None:
        return False
    stack, lex = state
    if lex is not None:
        if lex[0] != "num" or lex[1] not in _NUM_ACCEPT:
            return False
        stack = _value_done(stack)
    return stack == (("done",),)
