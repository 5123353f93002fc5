"""How late the open-loop driver submitted: 95th percentile of submit − due."""
import numpy as np


def read(run):
    lag = run.lag_s[~np.isnan(run.lag_s)]
    return run.pct(lag * 1e3, 95) if lag.size else None
