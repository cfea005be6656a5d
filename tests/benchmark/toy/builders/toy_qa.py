"""A builder made of new files only: the question-answering server as the
harness builds it, over the configuration's own model layouts, stamped so
that a test can see which builder ran."""

from harness.system import System, qa_rest_server


def build(config: dict, traffic: dict, seed: int) -> System:
    system = qa_rest_server(config, traffic, seed)
    system.built_by = "toy_qa"
    return system
