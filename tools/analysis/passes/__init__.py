"""Pass registry: rule name -> (exit bit, run callable).

Exit codes OR the bits of every rule with unbaselined, unsuppressed
findings, so `python -m tools.analysis; echo $?` names the failing
passes without parsing output (hotloop=1 clock=2 ownership=4
lockorder=8 surface=16 oneengine=32)."""

from . import clocks, hotloop, locks, oneengine, ownership, surface

PASSES = (
    (hotloop.RULE, hotloop.BIT, hotloop.run),
    (clocks.RULE, clocks.BIT, clocks.run),
    (ownership.RULE, ownership.BIT, ownership.run),
    (locks.RULE, locks.BIT, locks.run),
    (surface.RULE, surface.BIT, surface.run),
    (oneengine.RULE, oneengine.BIT, oneengine.run),
)

RULES = tuple(name for name, _, _ in PASSES)
BITS = {name: bit for name, bit, _ in PASSES}
