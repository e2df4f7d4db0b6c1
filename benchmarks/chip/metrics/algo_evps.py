"""Graphalytics' edges-plus-vertices per second: (|V| + |E|) of the
graph, its vertex ids and undirected edges, times the kernel runs
completed in the window, over the window's seconds."""


def read(run, suffix):
    runs = run.extra.get("window_runs")
    if not runs or run.window_s <= 0:
        return None
    return (run.dataset["n"] + run.dataset["n_edges"]) * runs / run.window_s
