"""Share of the window in which the program waited on an empty batch queue."""


def read(facts):
    return 100.0 * facts['window']['gen_wait_s'] / facts['window']['seconds']
