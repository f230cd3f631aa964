"""Op-level analyzer for compiled HLO module text (DESIGN.md §14).

Parses the text that ``jax.stages.Compiled.as_text()`` returns into a
table of instructions — (opcode, shape, sharding annotation, custom-call
target) — plus the module-level ``input_output_alias`` map, and checks
declarative contracts against it:

* :func:`check_no_collectives` — the §8 zero-collective decode contract.
  Asserted on parsed *opcodes* (with async ``-start``/``-done``/``-update``
  suffixes normalized away), not substrings: a substring grep
  false-negatives on renamed ops and false-positives on fusion names that
  merely mention a collective.
* :func:`check_no_host_ops` — no infeed/outfeed/send/recv and no
  host-callback ``custom-call`` inside a jitted serving path.
* :func:`check_donation` — every ``donate_argnums`` leaf actually aliases
  an output. XLA silently *drops* unusable donations; a dropped pool
  donation doubles the slot pool's HBM footprint without any error.

The parser is deliberately tolerant: lines that are not instructions
(computation headers, braces, comments, metadata continuation) are
skipped, so it works across XLA text-format drift.
"""
from __future__ import annotations

import dataclasses
import re

from repro.analysis.findings import Finding

# Cross-device collective opcodes (base names; async forms are the base
# plus -start/-done/-update, normalized by `base_opcode`).
COLLECTIVE_OPCODES = frozenset({
    "all-reduce", "all-gather", "all-to-all", "ragged-all-to-all",
    "reduce-scatter", "collective-permute", "collective-broadcast",
})

# Ops that move data to/from the host inside the compiled program.
HOST_TRANSFER_OPCODES = frozenset({
    "infeed", "outfeed", "send", "recv", "send-done", "recv-done",
})

# custom-call targets that re-enter python / the host runtime.
_HOST_CALLBACK_TARGET = re.compile(r"callback|host_", re.IGNORECASE)

_ASYNC_SUFFIXES = ("-start", "-done", "-update")


def base_opcode(opcode: str) -> str:
    """Normalize async variants: ``all-gather-start`` -> ``all-gather``."""
    for suf in _ASYNC_SUFFIXES:
        if opcode.endswith(suf):
            return opcode[: -len(suf)]
    return opcode


def is_collective(opcode: str) -> bool:
    return base_opcode(opcode) in COLLECTIVE_OPCODES


@dataclasses.dataclass(frozen=True)
class HloInstruction:
    name: str                      # %name (leading % stripped)
    opcode: str                    # e.g. "dynamic-update-slice"
    shape: str                     # raw result-shape text
    line: int                      # 1-based line in the module text
    sharding: str | None           # raw sharding={...} annotation, if any
    custom_call_target: str | None  # for custom-call ops
    text: str                      # the full instruction line


@dataclasses.dataclass
class HloModule:
    """Instruction table + entry-module attributes of one HLO module."""

    name: str
    instructions: list[HloInstruction]
    # output shape-index -> (param number, param shape-index, kind)
    input_output_alias: dict[tuple[int, ...],
                             tuple[int, tuple[int, ...], str]]
    text: str

    def opcodes(self) -> set[str]:
        return {i.opcode for i in self.instructions}

    def find(self, opcode: str) -> list[HloInstruction]:
        return [i for i in self.instructions if i.opcode == opcode]

    def collectives(self) -> list[HloInstruction]:
        return [i for i in self.instructions if is_collective(i.opcode)]

    def donated_params(self) -> set[tuple[int, tuple[int, ...]]]:
        """Distinct (param number, param shape-index) pairs that alias an
        output — the donations XLA actually honoured."""
        return {(p, pidx)
                for p, pidx, _kind in self.input_output_alias.values()}


_MODULE_RE = re.compile(r"^HloModule\s+([^\s,]+)")
_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%?(?P<name>[\w.\-]+)\s*=\s*")
_OPCODE_RE = re.compile(r"\s*(?P<op>[A-Za-z][\w\-]*)\(")
_SHARDING_RE = re.compile(r"sharding=(\{[^}]*\})")
_TARGET_RE = re.compile(r'custom_call_target="([^"]*)"')
_ALIAS_ENTRY_RE = re.compile(
    r"\{([\d,\s]*)\}:\s*\((\d+),\s*\{([\d,\s]*)\}(?:,\s*([\w\-]+))?\)")


def _index_tuple(raw: str) -> tuple[int, ...]:
    return tuple(int(x) for x in raw.replace(",", " ").split())


def _balanced(text: str, start: int) -> int:
    """Index one past the paren that closes ``text[start]`` ('(')."""
    depth = 0
    for i in range(start, len(text)):
        if text[i] == "(":
            depth += 1
        elif text[i] == ")":
            depth -= 1
            if depth == 0:
                return i + 1
    return len(text)


def _parse_alias_map(text: str) -> dict:
    """Parse ``input_output_alias={ {0}: (1, {}, may-alias), ... }``."""
    key = "input_output_alias={"
    at = text.find(key)
    if at < 0:
        return {}
    depth, i = 1, at + len(key)
    start = i
    while i < len(text) and depth:
        if text[i] == "{":
            depth += 1
        elif text[i] == "}":
            depth -= 1
        i += 1
    body = text[start:i - 1]
    out = {}
    for m in _ALIAS_ENTRY_RE.finditer(body):
        out_idx = _index_tuple(m.group(1))
        param = int(m.group(2))
        param_idx = _index_tuple(m.group(3))
        kind = m.group(4) or "may-alias"
        out[out_idx] = (param, param_idx, kind)
    return out


def parse_hlo(text: str) -> HloModule:
    """Parse HLO module text into an :class:`HloModule` op table."""
    name = ""
    instructions: list[HloInstruction] = []
    alias = _parse_alias_map(text)
    for lineno, line in enumerate(text.splitlines(), start=1):
        mod = _MODULE_RE.match(line)
        if mod:
            name = name or mod.group(1)
            continue
        m = _INSTR_RE.match(line)
        if not m:
            continue
        rest = line[m.end():]
        # Result shape: balanced parens for tuple shapes, else one token.
        if rest.startswith("("):
            end = _balanced(rest, 0)
        else:
            end = len(rest) - len(rest.lstrip())
            while end < len(rest) and not rest[end].isspace():
                end += 1
        shape, tail = rest[:end], rest[end:]
        op = _OPCODE_RE.match(tail)
        if not op:
            continue                     # not an instruction line
        sharding = _SHARDING_RE.search(line)
        target = _TARGET_RE.search(line)
        instructions.append(HloInstruction(
            name=m.group("name"), opcode=op.group("op"), shape=shape,
            line=lineno, sharding=sharding.group(1) if sharding else None,
            custom_call_target=target.group(1) if target else None,
            text=line.strip()))
    return HloModule(name=name, instructions=instructions,
                     input_output_alias=alias, text=text)


# ---------------------------------------------------------------------------
# Contract checks (DESIGN.md §14 rule catalog)
# ---------------------------------------------------------------------------


def check_no_collectives(module: HloModule, label: str) -> list[Finding]:
    """HLO001: zero cross-device collectives in the compiled program —
    the §8 sharded-decode contract."""
    return [Finding(rule="HLO001", path=label, line=i.line,
                    symbol=base_opcode(i.opcode),
                    message=f"collective {i.opcode} in {i.shape}")
            for i in module.collectives()]


def check_no_host_ops(module: HloModule, label: str) -> list[Finding]:
    """HLO002: no host transfers (infeed/outfeed/send/recv) and no
    host-callback custom-calls inside a jitted serving path."""
    out = []
    for i in module.instructions:
        if base_opcode(i.opcode) in HOST_TRANSFER_OPCODES:
            out.append(Finding(
                rule="HLO002", path=label, line=i.line,
                symbol=base_opcode(i.opcode),
                message=f"host transfer op {i.opcode}"))
        elif (i.opcode == "custom-call" and i.custom_call_target
              and _HOST_CALLBACK_TARGET.search(i.custom_call_target)):
            out.append(Finding(
                rule="HLO002", path=label, line=i.line,
                symbol=i.custom_call_target,
                message=f"host callback custom-call "
                        f"({i.custom_call_target})"))
    return out


def check_donation(module: HloModule, expected_leaves: int,
                   label: str) -> list[Finding]:
    """DON001: the compiled program honours fewer donations than the
    ``donate_argnums`` contract promised — XLA silently dropped some
    (shape/dtype mismatch with every output, or an unused input), which
    doubles that buffer's HBM footprint."""
    got = len(module.donated_params())
    if got < expected_leaves:
        return [Finding(
            rule="DON001", path=label, line=0, symbol=module.name,
            message=(f"only {got}/{expected_leaves} donated leaves alias "
                     f"an output (input_output_alias) — donation silently "
                     f"dropped"))]
    return []
