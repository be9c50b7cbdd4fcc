"""Share of the random-effect scores that took the SLOT form (re.score spans with form="slots": a gather of
the [E, S] table at each row's own slots, no [n, S] array), against the densified-subspace form ("subspace")
and the per-call search ("searched"): 100 over a sparse shard with ragged subspaces, 0 over a dense one.
None on a program whose re.score carries no form."""

UNIT = "%"
BETTER = "higher"
SOURCE = "program_span"
LAYER = "random-effect solve"
MOVES = "fit_s"


def read(obs):
    forms = [s.attrs["form"] for s in obs.spans_named("re.score") if "form" in s.attrs]
    return 100.0 * sum(f == "slots" for f in forms) / len(forms) if forms else None
