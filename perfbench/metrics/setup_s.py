"""setup_s — Process start of the benchmark to the window's start: native build or load,
agent start, backend up, warm-up of the cell's own geometries settled."""


def read(obs):
    return obs['t0'] - obs['t_start']
