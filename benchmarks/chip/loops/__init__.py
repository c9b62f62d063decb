"""Window loops, one per file, named by a traffic mix's ``loop`` key; see
``traffic.py`` for what each module provides."""
