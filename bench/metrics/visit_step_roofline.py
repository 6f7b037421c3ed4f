"""Share of the HBM-bandwidth roofline that full-precision scoring reaches
over the traced micro-batches: the bytes the scored rows need over the
chip's peak bandwidth times the device seconds of the stages that score
them.

The rows are ``compass_dist_total``: those the visit-step kernel scores in
the graph loop, and also those PREFILTER's ``filter_distance`` scan and the
mutable tier's delta scan score.  So the seconds are those of
``compass/visit_step`` (the XLA gather of its rows included,
``kernels/row_gather.py``), ``compass/filter_distance`` and
``compass/mutable/delta`` (its merge included).  A row needs its float32
vector (4 x dim bytes), its float32 attributes (4 x attrs) and its id; the
query and the predicate bounds stay in fast memory.  So the share is a
floor."""
from bench import cost, scopes

SCORING = ("compass/visit_step", "compass/filter_distance", "compass/mutable/delta")


def row_bytes(dim: int, attrs: int) -> int:
    """Bytes one full-precision row needs from memory."""
    return 4 * dim + 4 * attrs + cost.ID_BYTES


def read(run):
    att = scopes.stages(run)
    if att is None or run.peaks is None or run.config.get("quant"):
        return None
    corpus = run.config["corpus"]
    nbytes = run.counters.get("compass_dist_total", 0.0) * row_bytes(
        int(corpus["dim"]), int(corpus["attrs"]))
    return cost.roofline_share(nbytes, run.peaks["hbm_bytes_per_s"],
                               sum(att.stage_s.get(s, 0.0) for s in SCORING))
