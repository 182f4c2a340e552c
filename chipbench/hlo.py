"""Pure-text parser for compiled XLA HLO modules (``compiled.as_text()``).

A copy of ``repro.analysis.hlo``, kept with the benchmark so that the map
from trace events to ``jax.named_scope`` tags cannot change with the program.

Deliberately dependency-free (no jax import): the parser sees only the
dumped text, so it works on modules compiled elsewhere and the rule engine
can run on a saved ``--dump-hlo`` artifact. It extracts exactly what the
lint rules need, no more:

* computation blocks and the call graph between them (``to_apply=``,
  ``calls=``, ``condition=``/``body=``, conditional branch computations);
* per-instruction operand/result types with dtype bit-widths, so operand
  payload sizes are computable without executing anything;
* ``replica_groups``, ``metadata={op_name="..."}`` (which carries the
  ``jax.named_scope`` source tags through compilation), and the module
  header's ``input_output_alias`` map (donation).
"""

from __future__ import annotations

import dataclasses
import math
import re

__all__ = [
    "HloComputation",
    "HloInstruction",
    "HloModule",
    "dtype_bits",
    "parse_module",
    "parse_type",
]

# dtype token -> bits per element; anything absent falls back to the first
# digit group in the token (f8e4m3fn -> 8, bf16 -> 16) or 8 for pred
_DTYPE_BITS = {
    "pred": 8,
    "s4": 4,
    "u4": 4,
    "s8": 8,
    "u8": 8,
    "s16": 16,
    "u16": 16,
    "s32": 32,
    "u32": 32,
    "s64": 64,
    "u64": 64,
    "f16": 16,
    "bf16": 16,
    "f32": 32,
    "f64": 64,
    "c64": 64,
    "c128": 128,
    "token": 0,
}

_TYPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([\d,]*)\]")
_INSTR_RE = re.compile(r"^\s+(ROOT\s+)?%?([\w.-]+)\s*=\s*(.*)$")
_OPCODE_RE = re.compile(r"([a-zA-Z][\w-]*)\(")
_NAME_RE = re.compile(r"%([\w.-]+)")
_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
_ALIAS_ENTRY_RE = re.compile(
    r"\{([\d,\s]*)\}:\s*\((\d+),\s*\{([\d,\s]*)\}(?:,\s*([\w-]+))?\)"
)
_CALLEE_ATTRS = ("to_apply", "calls", "condition", "body")
_BRANCH_ATTRS = ("false_computation", "true_computation")


def dtype_bits(dtype: str) -> int:
    """Bits per element of an HLO dtype token (``s8`` -> 8)."""
    if dtype in _DTYPE_BITS:
        return _DTYPE_BITS[dtype]
    m = re.match(r"[a-z]+(\d+)", dtype)
    return int(m.group(1)) if m else 8


def parse_type(token: str) -> tuple[str, tuple[int, ...], int]:
    """``"s8[4,8]"`` -> ``("s8", (4, 8), 256)`` (dtype, dims, total bits)."""
    m = _TYPE_RE.match(token)
    if m is None:
        raise ValueError(f"not an HLO type token: {token!r}")
    dtype = m.group(1)
    dims = tuple(int(d) for d in m.group(2).split(",") if d)
    numel = math.prod(dims) if dims else 1
    return dtype, dims, numel * dtype_bits(dtype)


def _balanced(text: str, start: int, open_ch: str, close_ch: str) -> int:
    """Index one past the bracket closing ``text[start]`` (which must be
    ``open_ch``)."""
    depth = 0
    for j in range(start, len(text)):
        if text[j] == open_ch:
            depth += 1
        elif text[j] == close_ch:
            depth -= 1
            if depth == 0:
                return j + 1
    return len(text)


def _attr(attrs: str, name: str) -> str | None:
    """The value of ``name=...`` in an attribute tail: a ``%target`` name,
    or a balanced ``{...}`` / ``[..]<=[..]`` group literal, verbatim."""
    m = re.search(rf"\b{name}=", attrs)
    if m is None:
        return None
    j = m.end()
    if attrs[j : j + 1] == "{":
        return attrs[j : _balanced(attrs, j, "{", "}")]
    m2 = _NAME_RE.match(attrs, j) or re.match(r"[^,\s]+", attrs[j:])
    if m2 is None:
        return None
    return m2.group(1) if m2.re is _NAME_RE else m2.group(0)


@dataclasses.dataclass(frozen=True)
class HloInstruction:
    name: str
    opcode: str
    result_types: tuple[str, ...]
    operand_types: tuple[str, ...]
    operand_names: tuple[str, ...]
    computation: str
    callees: tuple[str, ...]
    branch_targets: tuple[str, ...]  # conditional only; index = branch id
    replica_groups: str | None
    op_name: str | None
    raw: str

    @property
    def operand_bits(self) -> int:
        """Total payload bits across array operands (per-device shapes —
        the module is the per-device SPMD program)."""
        return sum(parse_type(t)[2] for t in self.operand_types)

    @property
    def operand_dtypes(self) -> tuple[str, ...]:
        return tuple(parse_type(t)[0] for t in self.operand_types)


@dataclasses.dataclass(frozen=True)
class HloComputation:
    name: str
    instructions: tuple[HloInstruction, ...]


@dataclasses.dataclass(frozen=True)
class HloModule:
    name: str
    entry: str
    computations: dict[str, HloComputation]
    # output index -> (param index, param tuple index, kind), straight from
    # the header's input_output_alias (empty dict == nothing donated/aliased)
    input_output_alias: dict[str, tuple[int, str, str]]

    def reachable(self, root: str) -> set[str]:
        """Computation names transitively callable from ``root`` (callees
        and conditional branches), including ``root`` itself."""
        seen: set[str] = set()
        stack = [root]
        while stack:
            name = stack.pop()
            if name in seen or name not in self.computations:
                continue
            seen.add(name)
            for ins in self.computations[name].instructions:
                stack.extend(ins.callees)
                stack.extend(ins.branch_targets)
        return seen

    def instructions(self):
        for comp in self.computations.values():
            yield from comp.instructions

    def conditionals(self) -> list[HloInstruction]:
        return [i for i in self.instructions() if i.opcode == "conditional"]


def _find_opcode(rest: str):
    """The ``opcode(`` match after the result type. TPU layouts put
    ``T(8,128)`` tiles inside the type's ``{...}``, so matches inside
    braces are skipped (a change from ``repro.analysis.hlo``)."""
    depth = 0
    for j, ch in enumerate(rest):
        if ch == "{":
            depth += 1
        elif ch == "}":
            depth -= 1
        elif depth == 0 and ch.isalpha() and (j == 0 or not _WORD(rest[j - 1])):
            op = _OPCODE_RE.match(rest, j)
            if op is not None:
                return op
    return None


def _WORD(ch: str) -> bool:
    return ch.isalnum() or ch in "_.-"


def _parse_instruction(line: str, computation: str) -> HloInstruction | None:
    m = _INSTR_RE.match(line)
    if m is None:
        return None
    name, rest = m.group(2), m.group(3)
    op = _find_opcode(rest)
    if op is None:
        return None
    opcode = op.group(1)
    result_types = tuple(t.group(0) for t in _TYPE_RE.finditer(rest[: op.start()]))
    args_end = _balanced(rest, op.end() - 1, "(", ")")
    args = rest[op.end() : args_end - 1]
    attrs = rest[args_end:]
    callees = tuple(c for a in _CALLEE_ATTRS if (c := _attr(attrs, a)) is not None)
    if opcode == "conditional":
        listed = _attr(attrs, "branch_computations")
        if listed is not None:
            branch_targets = tuple(_NAME_RE.findall(listed))
        else:
            # (false, true) so the tuple index equals the jaxpr branch index
            branch_targets = tuple(
                c for a in _BRANCH_ATTRS if (c := _attr(attrs, a)) is not None
            )
    else:
        branch_targets = ()
    op_name = _OP_NAME_RE.search(line)
    return HloInstruction(
        name=name,
        opcode=opcode,
        result_types=result_types,
        operand_types=tuple(t.group(0) for t in _TYPE_RE.finditer(args)),
        operand_names=tuple(_NAME_RE.findall(args)),
        computation=computation,
        callees=callees,
        branch_targets=branch_targets,
        replica_groups=_attr(attrs, "replica_groups"),
        op_name=op_name.group(1) if op_name else None,
        raw=line.strip(),
    )


def parse_module(text: str) -> HloModule:
    """Parse ``compiled.as_text()`` into computations + call metadata."""
    module_name = ""
    alias: dict[str, tuple[int, str, str]] = {}
    computations: dict[str, list[HloInstruction]] = {}
    entry = ""
    current: str | None = None
    for line in text.splitlines():
        if line.startswith("HloModule"):
            nm = re.match(r"HloModule\s+([\w.-]+)", line)
            module_name = nm.group(1) if nm else ""
            am = re.search(r"input_output_alias=", line)
            if am is not None:
                blob = line[am.end() : _balanced(line, am.end(), "{", "}")]
                for out_idx, p_idx, p_tuple, kind in _ALIAS_ENTRY_RE.findall(blob):
                    alias[out_idx.strip() or "()"] = (
                        int(p_idx),
                        p_tuple.strip(),
                        kind or "may-alias",
                    )
            continue
        if not line[:1].isspace() and line.rstrip().endswith("{"):
            nm = _NAME_RE.search(line)
            if nm is not None:
                current = nm.group(1)
                computations[current] = []
                if line.startswith("ENTRY"):
                    entry = current
            continue
        if current is not None and line.strip() == "}":
            current = None
            continue
        if current is not None:
            ins = _parse_instruction(line, current)
            if ins is not None:
                computations[current].append(ins)
    return HloModule(
        name=module_name,
        entry=entry,
        computations={
            k: HloComputation(k, tuple(v)) for k, v in computations.items()
        },
        input_output_alias=alias,
    )
