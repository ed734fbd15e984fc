"""Layer kernels (ops/kbfs.py): of the distinct candidates the device's k-path
searches met, the share the simple-path rule dropped — the target on the
candidate's own prefix (``dgraph_kpath_candidates_total{loop}`` over all
fates, window deltas).  Nothing where the program has no such family or met
no candidate."""


def read(obs):
    by = obs.delta("dgraph_kpath_candidates_total")
    total = sum(by.values())
    return 100.0 * by.get("loop", 0.0) / total if total > 0 else None
