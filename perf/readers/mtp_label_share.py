"""The window's sum of the prediction module's label weights over the main objective's, of the step's device counter ``objective``."""


def read(facts):
    sums = facts['counters'].get('objective')
    if not sums or len(sums) < 4 or not sums[0]:  # [sum w, sum w2, sum w CE, sum w2 CE2]; a tower without a module keeps two
        return None
    return sums[1] / sums[0]
