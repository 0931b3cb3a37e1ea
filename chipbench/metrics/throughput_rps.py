"""Requests answered inside the window over the window's length (backlog
cells, where the server never waits for work)."""


def read(w):
    return w["completed"] / w["seconds"]
