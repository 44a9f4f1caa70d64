"""One module per kind of traffic: ``cells/<kind>.py`` defines ``Cell``,
which reads its mix from ``traffic/<name>.json``, builds the cell, runs its
window and decides ``correct``."""
