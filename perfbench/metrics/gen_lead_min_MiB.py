"""gen_lead_min_MiB — generator: least distance, over the window's polls of /debug/ledger, between
the generator's offset and the agent's read offset."""

from benchlib import observe


def read(obs):
    lag = observe.read_lag_bytes(obs)
    return None if lag is None else float(lag.min()) / (1 << 20)
