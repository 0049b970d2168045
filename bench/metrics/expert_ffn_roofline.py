"""Share of its roofline the held experts' grouped matmul reaches, in
percent.

The work is that of the detector batches that ran in the traced window:
the ``detector_step`` module's runs there, times each batch's mean held
pairs (the program's ``cascade.local_pairs`` over its batches, which
``cascade.routed_pairs`` counts), times the FLOPs and least bytes the
detector's reference module counts per pair and per expert layer
(``expert_pair_flops``, ``expert_pair_bytes``, ``expert_layer_bytes``).
Its least time, the larger of FLOPs over the peak and bytes over the HBM
bandwidth, is divided by the device time of the grouped-matmul operations
(``ragged-dot``) among the trace's costliest (``device_ops``). Each of
the gate, up and down projections is one such operation and a third of
the work, so only the thirds whose operation is listed count. None where
either side is missing."""

from bench import program, reference
from bench.trace import BACKBONE

#: the grouped matmul's operations in the device trace, and their number
OP, PROJECTIONS = "ragged-dot", 3


def read(ctx):
    d, tr = ctx["detector"], ctx["trace"]
    c = program.counters()
    if d is None or not c or not c.get("cascade.routed_pairs"):
        return None
    ops = [s for name, s in tr["device_ops"]
           if OP in name and "metadata" not in name]
    runs = sum(m["count"] for name, m in tr["modules"].items()
               if BACKBONE in name)
    if not ops or sum(ops) <= 0 or not runs:
        return None
    ref = reference.detector(ctx["config"])
    g = ctx["gate"]
    layers = d["n_layers"] - d["first_dense_layers"]
    batches = c["cascade.routed_pairs"] / (
        d["batch"] * ref.tokens(g, d) * d["top_k"] * layers)
    pairs = runs * c.get("cascade.local_pairs", 0) / batches
    p = ctx["peaks"]
    least = max(pairs * ref.expert_pair_flops(d) / p.bf16_flops,
                (pairs * ref.expert_pair_bytes(d)
                 + runs * layers * ref.expert_layer_bytes(d)) / p.hbm_bytes)
    return least * min(len(ops), PROJECTIONS) / PROJECTIONS / sum(ops) \
        * 100.0
