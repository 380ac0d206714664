from chipbench.harness import main

main()
