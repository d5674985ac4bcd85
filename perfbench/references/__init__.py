"""Plain references, one per app model, named by a configuration's
``reference`` key."""
