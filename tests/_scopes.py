"""Reading the program's named scopes back out of compiled HLO text."""
import re

_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = (.*)$', re.M)
_OP_NAME = re.compile(r'\bop_name="([^"]*)"')
_WRAPPED = re.compile(r"^[\w\-]+\((.*)\)$")


def op_names(hlo_text: str) -> dict:
    """Instruction name -> (op_name, rest of the line)."""
    out = {}
    for name, rhs in _INSTR.findall(hlo_text):
        m = _OP_NAME.search(rhs)
        if m:
            out[name] = (m.group(1), rhs)
    return out


def path(op_name: str) -> list:
    """``[(scope, transposed)]`` along an op_name, transforms unwrapped:
    ``transpose(jvp(model.blocks))`` is ``("model.blocks", True)``."""
    out = []
    for part in op_name.split("/"):
        transposed = part.startswith("transpose(")
        while (m := _WRAPPED.match(part)):
            part = m.group(1)
        out.append((part, transposed))
    return out
