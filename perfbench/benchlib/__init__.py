"""The chip benchmark's own library: cell lookup, traffic, weights, the
plain reference, trace reduction and the comparison that decides
``correct``.  Nothing here is edited by a PR that claims a gain."""
