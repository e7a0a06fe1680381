"""Programs compiled (or read from the persistent cache) inside the window: CompileMeter."""


def read(facts):
    return float(facts['compiled_in_window']['programs'])
