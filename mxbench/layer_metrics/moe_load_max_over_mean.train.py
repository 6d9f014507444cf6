"""Experts: the fullest held expert's routed rows over the mean of its
layer's experts, the worst expert layer's, from the per-expert row
counts the expert layers keep as auxiliary state, read once after the
window (the last step's routing). The mean is over all the router's
experts, held here or not: every token chooses top-k of them, so it is
tokens x top-k / routed experts whatever the routing
(``Run.expert_even``). 1 is even routing; the experts' one buffer holds
2.0 times the held experts' even share before the layer takes its dense
path; 0 where no held expert is routed a row (this chip's share
of the layer idles: the load is on experts other chips hold). Nothing
on a program without the counts."""
UNIT = "ratio"


def read(run):
    rows = getattr(run, "expert_rows", None)
    even = getattr(run, "expert_even", None)
    if not rows or not even:
        return None
    return max(float(r.max()) for r in rows.values()) / even
