"""Host milliseconds per step building the batch
(``SyntheticLMPipeline``)."""
import statistics


def read(run, records, summary):
    xs = records["window"]["input_s"]
    return 1e3 * statistics.fmean(xs) if xs else None
