"""Programs per statement template over the slice: distinct
``program.sig`` (the digest of the program-cache signature the statement
ran under) among the slice's history records, divided by the distinct
templates among them (a class's template is the ``template`` key of its
entry in the statement file, ``q3_p1`` -> ``q3``; a class without one is
its own template). 1.0 when every draw of a template runs the template's
one program; a draw that plans another static shape (another segment
selection, another survivor budget) adds one. None where no record
carries ``program``."""

import json
import os

LAYER = "compile (QueryEngine._cached_program, utils/compile_cache.py)"
UNIT = "count"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "setup_s"

_STATEMENTS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "..", "statements")


def _templates():
    """{class: template} over every statement set that names one."""
    out = {}
    for name in sorted(os.listdir(_STATEMENTS)):
        if name.endswith(".json"):
            with open(os.path.join(_STATEMENTS, name)) as f:
                classes = json.load(f).get("classes", {})
            out.update((cls, st["template"]) for cls, st in classes.items()
                       if isinstance(st, dict) and "template" in st)
    return out


def compute(run):
    template_of = _templates()
    sigs, templates = set(), set()
    for sample, rec in run["pairs"]:
        sig = (rec.get("program") or {}).get("sig")
        if sig:
            sigs.add(sig)
            templates.add(template_of.get(sample["cls"], sample["cls"]))
    return len(sigs) / len(templates) if templates else None
