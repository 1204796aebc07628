"""The chip benchmark of CoLA: ``python bench/run.py --workload <name> ...``."""
