"""The benchmark's harness: the yardstick later PRs may not change.

Nothing in here knows a cell, a configuration, a traffic mix, a query
class or a layer metric by name: those are files under
`benchmark/{configs,traffic,classes,layer_metrics}/` found through
`BENCHMARK.json`. Modules that the load-generator child imports
(`spec`, `datagen`, `schedule`, `promwire`, `loadgen`) import neither
JAX nor the program."""
