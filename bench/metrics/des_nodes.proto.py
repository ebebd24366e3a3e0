"""Branch-and-bound nodes the exact DES explored per protocol pass: the
sum of `RoundSchedule.des_nodes` over the pass's rounds, as a mean over
the passes of the traced window.  A count."""


def read(obs):
    nodes = obs.get("des_nodes")
    if not nodes:
        return None
    return sum(nodes) / len(nodes)
