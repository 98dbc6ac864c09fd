"""Recovery, from inside: how many subtasks the kill phase's recovery
rebuilt together (``RecoveryReport.victims``, also the counter
``recovery.victims``): 4 on ``nexmark-q3-x4``, one subtask of every
vertex on the auctions' path. None on a program whose report does not
say."""


def read(run):
    victims = getattr(run.report, "victims", None)
    return None if victims is None else float(victims)
