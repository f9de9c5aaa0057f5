"""Cell drivers, one per kind of traffic: ``run(Run) -> Outcome``."""
