"""Load kinds: ``bench/load/<kind>.py`` drives one kind of traffic mix.

Each module has ``drive(sess, mix, seconds, rng) -> Outcome``; a traffic
file's ``kind`` names the module. The arrival and popularity arithmetic they
share is in ``bench.traffic_gen``."""
