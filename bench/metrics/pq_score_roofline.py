"""Share of the HBM-bandwidth roofline that the ADC scoring kernel reaches
over the traced micro-batches: the bytes its rows need
(``cost.pq_score_bytes`` of ``compass_adc_total``) over the chip's peak
bandwidth times the kernel's device seconds (``compass/pq_score``)."""
from bench import cost, scopes


def read(run):
    att = scopes.stages(run)
    if att is None or run.peaks is None or not run.config.get("quant"):
        return None
    nbytes = cost.pq_score_bytes(run.counters.get("compass_adc_total", 0.0),
                                 int(run.config["quant"]["m"]))
    return cost.roofline_share(nbytes, run.peaks["hbm_bytes_per_s"],
                               att.stage_s.get("compass/pq_score", 0.0))
