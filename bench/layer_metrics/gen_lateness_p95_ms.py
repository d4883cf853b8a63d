"""Load generator: 95th percentile of how late each request due in the
window was sent (send - due), in ms.  Host clock."""
from bench.measure import lateness_ms, percentile


def read(run):
    return percentile(lateness_ms(run.due), 95)
