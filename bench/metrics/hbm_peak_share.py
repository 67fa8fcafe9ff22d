"""The compiler's peak memory of the largest bucket the window ran
(``memory_analysis().peak_memory_in_bytes``, the fullest device) over the
device's ``bytes_limit``, in %."""


def read(run):
    peaks = [run["peaks"][s["bucket"]] for s in run["steps"]
             if s["bucket"] in run["peaks"]]
    if not peaks or not run.get("bytes_limit"):
        return None
    return 100.0 * max(peaks) / run["bytes_limit"]
