"""Job kinds, one module each, chosen by a configuration's ``job`` key."""
