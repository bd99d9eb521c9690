SELECT c_last_name, c_first_name, substr(s_city, 1, 30) AS city,
       ss_ticket_number, amt, profit
FROM (SELECT ss_ticket_number, ss_customer_sk, s_city,
             SUM(ss_coupon_amt) AS amt, SUM(ss_net_profit) AS profit
      FROM store_sales, date_dim, store, household_demographics
      WHERE ss_sold_date_sk = d_date_sk AND ss_store_sk = s_store_sk
        AND ss_hdemo_sk = hd_demo_sk
        AND (hd_dep_count = 6 OR hd_vehicle_count > 2)
        AND d_dow = 1 AND d_year IN (1999, 2000, 2001)
        AND s_number_employees BETWEEN 200 AND 295
      GROUP BY ss_ticket_number, ss_customer_sk, ss_addr_sk, s_city) ms,
     customer
WHERE ss_customer_sk = c_customer_sk
ORDER BY c_last_name, c_first_name, city, profit, ss_ticket_number
LIMIT 100
