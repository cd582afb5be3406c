"""Kinds of traffic, one module each: ``kinds/<kind>.py`` builds the
system under test for a configuration, makes the requests of a traffic
mix from the seed, serves them and checks the answers against the plain
reference.  A traffic mix (``traffic/<mix>.json``) names its kind and
holds only parameters."""
