"""Chip benchmark of the SSB query server: cells, configurations, traffic
mixes and per-layer metrics are data files found by name (``spec``);
``run`` measures one cell on the machine it is started on."""
