"""agent_cpu_cores — host: the agent's CPU seconds over the window's seconds."""

from benchlib import observe


def read(obs):
    return observe.agent_cpu_cores(obs)
